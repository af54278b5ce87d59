"""The program's own profiler spans and counters (DESIGN.md §13).

Each phase of a scoring step, of FedGenGMM and of the round driver is a
``jax.profiler.TraceAnnotation`` named ``repro.*``, its counters among
the annotation's arguments. A CPU trace of one call of each holds every
span, nested as the code nests them, with counters equal to what the
shapes give. A traced call returns what an untraced one returns. Inside
the jitted round loop the phases are ``jax.named_scope``\\ s, which the
lowered program's locations carry."""
import glob
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DEM, FedGenGMM, FitConfig
from repro.core.dem import DEMStrategy
from repro.core.gmm import GMM
from repro.core.partition import partition
from repro.data.sources import ArraySource
from repro.core.em import computed_lanes, fit_prepared_bytes
from repro.core.kmeans import SEED_ROWS
from repro.fed.runtime import (ShardedClients, _iterate_jit, make_backend,
                               slab_counters)
from repro.fed.strategies import FedKMeansStrategy
from repro.kernels.ops import BLOCK_N, LANES, padded_lanes, slab_bytes
from repro.serve import ScoreConfig, ScoreRequest, ScoringEngine
from conftest import planted_gmm_data

D, K, CLIENTS = 4, 3, 3


def traced(tmp_path, fn):
    """Run ``fn`` under the profiler -> (its value, the ``repro.*`` host
    spans as (name, start_ns, end_ns, counters), outer before inner)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in data.planes if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.")]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def parents(spans) -> dict:
    """{span name: the names of the innermost spans that enclose it}."""
    out = {}
    for i, (name, a, b, _) in enumerate(spans):
        around = [s[0] for s in spans[:i] if s[1] <= a and b <= s[2]]
        out.setdefault(name, set()).add(around[-1] if around else None)
    return out


def counters(spans, name) -> list:
    return [s[3] for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def split():
    x, y, _ = planted_gmm_data(np.random.default_rng(3), n=600, d=D, k=K,
                               spread=5.0)
    return partition(np.random.default_rng(4), x, y, CLIENTS, "dirichlet",
                     1.0)


# -- serving -------------------------------------------------------------

SLOTS, ROWS_PER_SLOT = 4, 8
# the fifth request has no rows and retires at admission; the sixth finds
# every slot taken
SIZES = (3, 8, 20, 0, 5, 7)


def _engine():
    rng = np.random.default_rng(0)
    gmm = GMM(jnp.full((K,), 1.0 / K), jnp.asarray(rng.normal(size=(K, D)),
                                                   jnp.float32),
              jnp.ones((K, D), jnp.float32))
    engine = ScoringEngine(gmm, ScoreConfig(mode="anomaly", slots=SLOTS,
                                            rows_per_slot=ROWS_PER_SLOT))
    rows = rng.normal(size=(max(SIZES), D)).astype(np.float32)
    engine.run([ScoreRequest(-1, rows[:2])])  # compile outside the trace
    return engine, [ScoreRequest(i, rows[:n]) for i, n in enumerate(SIZES)]


def test_serving_step_spans_nest_and_count(tmp_path):
    engine, requests = _engine()
    for r in requests:
        engine.submit(r)
    waited = 0.02
    time.sleep(waited)
    _, spans = traced(tmp_path, engine.step)
    tree = parents(spans)
    assert tree.pop("repro.serve.step") == {None}
    assert tree == {name: {"repro.serve.step"} for name in (
        "repro.serve.admit", "repro.serve.stage", "repro.serve.put",
        "repro.serve.score", "repro.serve.fetch", "repro.serve.harvest")}
    stage, = counters(spans, "repro.serve.stage")
    assert (stage["admitted"], stage["queued"]) == (5, 1)
    assert stage["queue_wait_us"] >= 5 * waited * 1e6
    put, = counters(spans, "repro.serve.put")
    assert put == {"rows": 3 + 8 + 8 + 5,
                   "rows_computed": SLOTS * ROWS_PER_SLOT,
                   "h2d_bytes": 4 * SLOTS * ROWS_PER_SLOT * (D + 1)}


def test_a_step_without_work_stages_nothing(tmp_path):
    engine, _ = _engine()
    _, spans = traced(tmp_path, engine.step)
    assert [s[0] for s in spans] == ["repro.serve.step", "repro.serve.admit",
                                     "repro.serve.stage"]
    assert counters(spans, "repro.serve.stage") == [
        {"admitted": 0, "queue_wait_us": 0, "queued": 0}]


def test_traced_scores_equal_untraced(tmp_path):
    scores = []
    for trace in (False, True):
        engine, requests = _engine()
        run = lambda: {r.rid: r.scores for r in engine.run(requests)}
        scores.append(traced(tmp_path / "t", run)[0] if trace else run())
    assert scores[0].keys() == scores[1].keys() == set(range(len(SIZES)))
    for rid, s in scores[0].items():
        np.testing.assert_array_equal(s, scores[1][rid])


def test_latency_runs_from_submit():
    engine, requests = _engine()
    engine.submit(requests[0])
    time.sleep(0.02)
    result, = engine.step()
    assert result.latency_s >= 0.02


# -- fitting -------------------------------------------------------------

def _slab(split):
    """The counters of a fit on the CPU, where the E-step runs the
    reference path over the d features themselves."""
    return {"clients": CLIENTS, "rows": int(np.sum(split.sizes)),
            "rows_computed": CLIENTS * split.data.shape[1], "lanes": D,
            "lanes_computed": D}


def _ragged_rows(split):
    """The rows the fused E-step computes: each client's rows rounded up
    to the kernel's row block, the blocks past them skipped."""
    return int(np.sum(-(-np.asarray(split.sizes) // BLOCK_N))) * BLOCK_N


def test_lanes_computed_is_the_lane_multiple():
    assert LANES == 128
    assert [padded_lanes(d) for d in (1, 38, 84, 128, 129)] == \
        [128, 128, 128, 128, 256]


@pytest.mark.parametrize("strategy,lanes", [
    (DEMStrategy(k=K, backend="fused"), LANES),
    (DEMStrategy(k=K, backend="reference"), D),
    # the fused E-step has no full covariance: the reference path runs
    (DEMStrategy(k=K, backend="fused", covariance_type="full"), D),
    (FedKMeansStrategy(k=K, assign_backend="fused"), LANES),
    (FedKMeansStrategy(k=K, assign_backend="reference"), D),
])
def test_lanes_computed_follows_the_backend(strategy, lanes):
    assert strategy.lanes_computed(D) == lanes


@pytest.mark.parametrize("backend,lanes", [("fused", LANES),
                                           ("reference", D)])
def test_fedgen_lanes_follow_its_estep(backend, lanes):
    assert computed_lanes(
        D, FitConfig(backend=backend).resolved_estep()) == lanes


def test_dem_reference_backend_counts_its_own_width(tmp_path, split):
    dem = DEM(K, config=FitConfig(max_iter=3, backend="reference"))
    _, spans = traced(tmp_path, lambda: dem.run(split,
                                                 key=jax.random.key(0)))
    assert counters(spans, "repro.rounds.loop") == [_slab(split)]


@pytest.mark.parametrize("backend", ["fused", "reference"])
def test_prepared_bytes_count_the_client_slab(tmp_path, split, backend):
    """The fused E-step's loops pad every client's rows once: the local
    fits' span and the round loop's count that slab's bytes. The
    reference path pads nothing and counts nothing."""
    config = FitConfig(max_iter=3, backend=backend)
    fed = FedGenGMM(k_clients=K, k_global=K, h=20, config=config)
    dem = DEM(K, config=config)
    _, spans = traced(tmp_path, lambda: (
        fed.run(split, key=jax.random.key(0)),
        dem.run(split, key=jax.random.key(0))))
    want = ([CLIENTS * slab_bytes(split.data.shape[1], D)]
            if backend == "fused" else [])
    for name in ("repro.fedgen.local", "repro.rounds.loop"):
        assert [c["prepared_bytes"] for c in counters(spans, name)
                if "prepared_bytes" in c] == want, name
    # the fed-kmeans init assigns on "auto", the reference path on a CPU
    assert all("prepared_bytes" not in c
               for c in counters(spans, "repro.rounds.init"))


@pytest.fixture(scope="module")
def wide_split():
    """Clients of several kernel row blocks each, of unequal sizes."""
    x, y, _ = planted_gmm_data(np.random.default_rng(5), n=3000, d=D, k=K,
                               spread=5.0)
    return partition(np.random.default_rng(6), x, y, CLIENTS, "dirichlet",
                     1.0)


@pytest.mark.parametrize("backend", ["fused", "reference"])
def test_rows_computed_skip_each_clients_padded_blocks(tmp_path, wide_split,
                                                       backend):
    """The fused E-step computes each client's row blocks up to its last
    row: the local fits' span and the round loop's count Σ ⌈n_c/512⌉·512
    rows. The reference path computes the whole padded slab."""
    split = wide_split
    n = split.data.shape[1]
    assert _ragged_rows(split) < CLIENTS * n
    config = FitConfig(max_iter=2, backend=backend)
    fed = FedGenGMM(k_clients=K, k_global=K, h=20, config=config)
    dem = DEM(K, config=config)
    _, spans = traced(tmp_path, lambda: (
        fed.run(split, key=jax.random.key(0)),
        dem.run(split, key=jax.random.key(0))))
    want = _ragged_rows(split) if backend == "fused" else CLIENTS * n
    for name in ("repro.fedgen.local", "repro.rounds.loop"):
        assert [c["rows_computed"] for c in counters(spans, name)] == \
            [want], name
    # a sampled cohort's sizes are not known on the host: every padded row
    backend = make_backend(split)
    assert slab_counters(backend, LANES, cohort_size=2,
                         row_block=BLOCK_N)["rows_computed"] == 2 * n


def test_prepared_bytes_on_the_chip(monkeypatch, split):
    """Where "auto" picks the kernels: the fed-kmeans init pads the
    clients' rows (no weight column), the round loop the E-step's slab; a
    local fit also pads its k-means seeding subsample once it has more
    rows. Chunked rows, full covariance and source clients pad per call."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    backend = make_backend(split)
    n = split.data.shape[1]
    dem = DEMStrategy(k=K)
    assert dem.prepared_bytes(backend, "init") == \
        CLIENTS * slab_bytes(n, D, weights=False)
    assert dem.prepared_bytes(backend, "loop") == CLIENTS * slab_bytes(n, D)
    assert DEMStrategy(k=K, chunk=64).prepared_bytes(backend, "loop") is None
    assert DEMStrategy(k=K, covariance_type="full").prepared_bytes(
        backend, "loop") is None
    sources = make_backend([ArraySource(np.asarray(split.data[0]))])
    assert dem.prepared_bytes(sources, "init") is None
    big, seed = SEED_ROWS + 1, slab_bytes(SEED_ROWS, D, weights=False)
    assert fit_prepared_bytes(big, D, FitConfig()) == \
        slab_bytes(big, D) + seed
    assert fit_prepared_bytes(big, D, FitConfig(chunk_size=64)) == seed
    assert fit_prepared_bytes(n, D, FitConfig(covariance_type="full")) == \
        slab_bytes(n, D, weights=False)


def test_prepared_bytes_per_chip_on_sharded_clients(monkeypatch, split):
    """On sharded clients a phase counts the slabs one chip builds: its
    own clients'."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = split.data.shape[1]
    three_shards = SimpleNamespace(shape={"data": CLIENTS})
    backend = ShardedClients(jnp.asarray(split.data),
                             jnp.asarray(split.mask), three_shards)
    dem = DEMStrategy(k=K)
    assert (backend.shards, backend.clients_per_shard) == (CLIENTS, 1)
    assert dem.prepared_bytes(backend, "init") == \
        slab_bytes(n, D, weights=False)
    assert dem.prepared_bytes(backend, "loop") == slab_bytes(n, D)


def test_sharded_dem_spans_count_the_mesh(tmp_path, split):
    """DEM on a mesh: the init and the loop count the shards and their
    clients; the init the bytes its all_gather brings (every client's K
    centers and sizes), the loop the bytes of one round's psum (one
    client's statistics, summed) and each chip's slabs."""
    mesh = jax.make_mesh((1,), ("data",))
    dem = DEM(K, mesh=mesh, config=FitConfig(max_iter=3, backend="fused"))
    res, spans = traced(tmp_path, lambda: dem.run(split,
                                                  key=jax.random.key(0)))
    assert parents(spans) == {name: {None} for name in (
        "repro.rounds.init", "repro.rounds.loop", "repro.rounds.finalize")}
    mesh_counters = {"shards": 1, "clients_per_shard": CLIENTS}
    # the fed-kmeans init assigns on "auto", the reference path on a CPU
    assert counters(spans, "repro.rounds.init") == [dict(
        mesh_counters, allgather_bytes=CLIENTS * (K * D + K) * 4)]
    assert counters(spans, "repro.rounds.loop") == [dict(
        _slab(split), rows_computed=_ragged_rows(split),
        lanes_computed=LANES, **mesh_counters,
        prepared_bytes=CLIENTS * slab_bytes(split.data.shape[1], D),
        allreduce_bytes=(K + 2 * K * D + 2) * 4)]
    assert counters(spans, "repro.rounds.finalize") == [
        {"rounds": int(res.n_rounds)}]


def test_fedgen_spans_nest_and_count(tmp_path, split):
    fed = FedGenGMM(k_clients=K, k_global=K, h=20,
                    config=FitConfig(max_iter=5))
    res, spans = traced(tmp_path, lambda: fed.run(split,
                                                  key=jax.random.key(0)))
    assert parents(spans) == {name: {None} for name in (
        "repro.rounds.init", "repro.fedgen.local", "repro.fedgen.unstack",
        "repro.fedgen.merge_sample", "repro.fedgen.refit",
        "repro.rounds.finalize")}
    assert [s[0] for s in spans] == [
        "repro.rounds.init", "repro.fedgen.local", "repro.fedgen.unstack",
        "repro.fedgen.merge_sample", "repro.fedgen.refit",
        "repro.rounds.finalize"]
    assert counters(spans, "repro.fedgen.local") == [_slab(split)]
    assert counters(spans, "repro.fedgen.merge_sample") == [
        {"rows": 20 * CLIENTS * K}]
    assert counters(spans, "repro.rounds.finalize") == [{"rounds": 1}]
    # the server refit's own result rides along
    assert res.global_result.gmm is res.global_gmm
    assert 1 <= int(res.global_result.n_iter) <= 5


def test_fedgen_traced_equals_untraced(tmp_path, split):
    fed = FedGenGMM(k_clients=K, k_global=K, h=20,
                    config=FitConfig(max_iter=5))
    plain = fed.run(split, key=jax.random.key(1))
    res, _ = traced(tmp_path, lambda: fed.run(split, key=jax.random.key(1)))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dem_spans_nest_and_count(tmp_path, split):
    dem = DEM(K, config=FitConfig(max_iter=6))
    res, spans = traced(tmp_path, lambda: dem.run(split,
                                                  key=jax.random.key(0)))
    assert parents(spans) == {name: {None} for name in (
        "repro.rounds.init", "repro.rounds.loop", "repro.rounds.finalize")}
    assert counters(spans, "repro.rounds.loop") == [_slab(split)]
    assert counters(spans, "repro.rounds.finalize") == [
        {"rounds": int(res.n_rounds)}]


def test_host_rounds_nest_in_the_loop(tmp_path, split):
    sources = [ArraySource(np.asarray(split.data[c, :n]))
               for c, n in enumerate(split.sizes)]
    dem = DEM(K, config=FitConfig(max_iter=3, chunk_size=64))
    res, spans = traced(tmp_path, lambda: dem.run(sources,
                                                  key=jax.random.key(0)))
    assert parents(spans) == {"repro.rounds.init": {None},
                              "repro.rounds.loop": {None},
                              "repro.rounds.round": {"repro.rounds.loop"},
                              "repro.rounds.finalize": {None}}
    assert len(counters(spans, "repro.rounds.round")) == int(res.n_rounds)
    # source clients are not padded into a slab
    slab = _slab(split)
    del slab["rows_computed"]
    assert counters(spans, "repro.rounds.loop") == [slab]


def test_a_sampled_cohort_counts_its_slab_only(split):
    backend = make_backend(split)
    assert slab_counters(backend, LANES, cohort_size=2) == {
        "clients": 2, "rows_computed": 2 * split.data.shape[1], "lanes": D,
        "lanes_computed": LANES}
    # where no layer gives the computed width, the counter is left out
    assert "lanes_computed" not in slab_counters(backend)


def test_round_loop_carries_its_named_scopes(split):
    strategy = DEMStrategy(k=K)
    backend = make_backend(split)
    state0 = strategy.init_state(jax.random.key(0), backend)
    text = _iterate_jit.lower(strategy, backend, state0, 4).as_text(
        debug_info=True)
    # the bootstrap round and the loop's body: each client's update inside
    # the reduce, the sum over clients, the server's combine
    for body in ("jit(_iterate_jit)/", "jit(_iterate_jit)/while/body/"):
        for scope in ("reduce/vmap(client_step)/", "reduce/reduce_sum",
                      "combine/"):
            assert f'"{body}{scope}' in text, body + scope
