"""Ahead-of-time compiles of the main path's Pallas kernels for a described
TPU v5e, at the widths the paper deployments run.

Nothing runs: each kernel is lowered for a chip that is described, not
attached, and compiled by the TPU compiler installed beside JAX. That
catches what interpret mode cannot (tile alignment, VMEM limits, a kernel
the compiler refuses) at no chip time. Each compiled program must hold the
Mosaic kernel itself (``tpu_custom_call``), not an interpret-mode loop.

The fit loops are compiled whole as well: their kernel operands must be
padded once, before ``lax.while_loop``, never inside a loop body, and a
loop's E-step over many clients is one kernel call over all of them,
never a loop that slices one client's slab at a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.config import FitConfig
from repro.core.dem import DEMStrategy
from repro.core.em import init_from_means_sharded
from repro.core.fedgen import _train_locals_jit
from repro.core.gmm import GMM
from repro.core.kmeans import federated_kmeans_sharded
from repro.fed.runtime import ShardedClients, SplitClients, _iterate_jit
from repro.kernels import ops

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The 2x2 host as one mesh axis, ``data``, as a four-chip cell
    shards its clients."""
    return Mesh(np.array(topo.devices), ("data",))


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, F32, sharding=sharding)


def _assert_kernel(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,d,k", [(65536, 84, 10), (65536, 24, 30)])
def test_estep_stats_compiles(one_chip, n, d, k):
    _assert_kernel(functools.partial(ops.estep_stats, interpret=False),
                   _spec(one_chip, n, d), _spec(one_chip, k, d),
                   _spec(one_chip, k, d), _spec(one_chip, k),
                   _spec(one_chip, n))


def test_gmm_logpdf_compiles(one_chip):
    n, d, k = 65536, 84, 10
    _assert_kernel(functools.partial(ops.gmm_logpdf, interpret=False),
                   _spec(one_chip, n, d), _spec(one_chip, k, d),
                   _spec(one_chip, k, d), _spec(one_chip, k))


def test_kmeans_assign_compiles(one_chip):
    n, d, k = 65536, 84, 10
    _assert_kernel(functools.partial(ops.kmeans_assign, interpret=False),
                   _spec(one_chip, n, d), _spec(one_chip, k, d))


def test_vmapped_estep_stats_compiles(one_chip):
    """The fused E-step as the split-client backend runs it: vmapped over
    a (clients, rows, d) slab, one shared model."""
    c, n, d, k = 20, 65536, 84, 10

    def clients(x, w, means, variances, log_weights):
        return jax.vmap(lambda xc, wc: ops.estep_stats(
            xc, means, variances, log_weights, wc, interpret=False))(x, w)

    _assert_kernel(clients, _spec(one_chip, c, n, d), _spec(one_chip, c, n),
                   _spec(one_chip, k, d), _spec(one_chip, k, d),
                   _spec(one_chip, k))


# -- the fit loops: kernel operands padded once, outside every loop body ----

C, N, K = 2, 4000, 10   # N is no multiple of the kernels' 512-row block


@pytest.fixture
def on_tpu(monkeypatch):
    """The program as it resolves on the chip: "auto" backends pick the
    kernels and the wrappers compile them for Mosaic, not interpret mode
    (both ask ``jax.default_backend()``). Traces made under the CPU's
    answer are dropped, before and after."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    monkeypatch.undo()
    jax.clear_caches()


_CALLS = re.compile(r"(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)"
                    r"|branch_computations=\{([^}]*)\}")
_PAD_OR_COPY = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                          r"(pad|copy|dynamic-slice)\(")


def loop_lines(hlo: str) -> list:
    """The instructions of every ``while`` body of the compiled program,
    and of the computations such a body calls."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if name is None and head:
            name, comps[head.group(1)] = head.group(1), []
        elif name is not None and line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    todo = [m.group(1) for lines in comps.values() for line in lines
            for m in [re.search(r"\bwhile\(.*\bbody=%([\w.\-]+)", line)]
            if m]
    seen = set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            for one, many in _CALLS.findall(line):
                todo.extend([one] if one else
                            [c.strip().lstrip("%") for c in many.split(",")])
    return [line for comp in seen for line in comps[comp]]


def loop_pads(hlo: str) -> list:
    """(op, shape) of every ``pad``, ``copy`` and ``dynamic-slice`` in a
    ``while`` body of the compiled program, or in a computation such a
    body calls."""
    found = []
    for line in loop_lines(hlo):
        m = _PAD_OR_COPY.match(line)
        if m:
            shape = tuple(int(v) for v in m.group(1).split(",") if v)
            found.append((m.group(2), shape))
    return found


_COLLECTIVE = re.compile(r" ((?:all-reduce|all-gather|all-to-all|"
                         r"collective-permute|reduce-scatter)"
                         r"(?:-start|-done)?)\(")


def collectives(lines) -> list:
    """(op, operand count) of every cross-chip collective among HLO
    instruction lines; a tuple collective counts its operands."""
    found = []
    for line in lines:
        m = _COLLECTIVE.search(line)
        if m:
            operands = line[m.end():].split(")", 1)[0]
            found.append((m.group(1), operands.count("%")))
    return found


def _assert_slab_built_once(hlo: str, n: int, d: int):
    """The kernel runs, and no loop rebuilds or slices the client slab:
    neither the padded rows ``(n_pad, 128·)`` nor the ``(n, 1)`` weight
    column, before or after its padding."""
    assert "tpu_custom_call" in hlo
    n_pad = -(-n // ops.BLOCK_N) * ops.BLOCK_N
    slab = {(n_pad, ops.padded_lanes(d)), (n, 1), (n_pad, 1)}
    in_loops = [(op, shape) for op, shape in loop_pads(hlo)
                if shape[-2:] in slab]
    assert in_loops == [], in_loops


def _assert_one_ragged_estep(hlo: str, clients: int):
    """The loop bodies hold one Mosaic E-step call, over all ``clients``
    at once, whose first operand is their ``(clients,)`` int32 count of
    row blocks to compute."""
    calls = [line for line in loop_lines(hlo)
             if re.match(r"\s*%estep_stats_pallas[.\d]* = ", line)]
    assert len(calls) == 1, calls
    assert 'custom_call_target="tpu_custom_call"' in calls[0]
    assert f"operand_layout_constraints={{s32[{clients}]{{0}}, " in calls[0]


def test_loop_pads_reads_while_bodies():
    hlo = """%body (p: f32[4]) -> f32[4] {
  %a = f32[8,128]{1,0} pad(%p, %z), padding=0_4x0_0
  %b = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused
  %s = f32[1,512,128]{2,1,0} dynamic-slice(%p, %i)
}

%fused (q: f32[4]) -> f32[4] {
  ROOT %c = f32[512,1]{1,0:T(8,128)} copy(%q)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %d = f32[9,9]{1,0} pad(%x, %z), padding=0_5x0_5
  ROOT %w = f32[4] while(%x), condition=%cond, body=%body
}
"""
    assert sorted(loop_pads(hlo)) == [("copy", (512, 1)),
                                      ("dynamic-slice", (1, 512, 128)),
                                      ("pad", (8, 128))]


def test_fedgen_local_fits_pad_once(one_chip, on_tpu):
    """FedGenGMM's vmapped local fits: the k-means init's Lloyd loops and
    the EM loop read slabs built before they iterate."""
    d = 84
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    config = FitConfig(backend="fused").resolved_for("em").replace(
        seed=0, init="auto")
    hlo = _train_locals_jit.lower(
        key, _spec(one_chip, C, N, d), _spec(one_chip, C, N), k=K,
        config=config).compile().as_text()
    _assert_slab_built_once(hlo, N, d)


def test_wadi_local_fits_run_one_ragged_kernel(one_chip, on_tpu):
    """FedGenGMM's vmapped local fits at WADI's shapes (20 clients, a
    151,552 x 128 slab each): the EM loop's E-step is one kernel over the
    20 clients with their block counts, on slabs built before the loop."""
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    config = FitConfig(backend="fused").resolved_for("em").replace(
        seed=0, init="auto")
    hlo = _train_locals_jit.lower(
        key, _spec(one_chip, WADI_C, WADI_N, WADI_D),
        _spec(one_chip, WADI_C, WADI_N), k=K,
        config=config).compile().as_text()
    _assert_slab_built_once(hlo, WADI_N, WADI_D)
    _assert_one_ragged_estep(hlo, WADI_C)


def _state0(strategy, d, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding,
                                       weak_type=s.weak_type),
        jax.eval_shape(lambda: strategy.state_from_gmm(
            GMM(jnp.full((K,), 1.0 / K), jnp.zeros((K, d)),
                jnp.ones((K, d))), dtype=F32)))


def test_dem_round_loop_pads_once(one_chip, on_tpu):
    """DEM's resident round loop: every round's E-step reads the clients'
    slabs, prepared before the loop."""
    d = 38
    strategy = DEMStrategy(k=K)
    backend = SplitClients(_spec(one_chip, C, N, d), _spec(one_chip, C, N))
    hlo = _iterate_jit.lower(strategy, backend, _state0(strategy, d, one_chip),
                             200).compile().as_text()
    _assert_slab_built_once(hlo, N, d)


def test_wadi_dem_round_loop_runs_one_ragged_kernel(one_chip, on_tpu):
    """DEM's round loop over WADI's 20 resident clients: a round's E-step
    is one kernel over every client with their block counts."""
    strategy = DEMStrategy(k=K)
    backend = SplitClients(_spec(one_chip, WADI_C, WADI_N, WADI_D),
                           _spec(one_chip, WADI_C, WADI_N))
    hlo = _iterate_jit.lower(
        strategy, backend, _state0(strategy, WADI_D, one_chip),
        200).compile().as_text()
    _assert_slab_built_once(hlo, WADI_N, WADI_D)
    _assert_one_ragged_estep(hlo, WADI_C)


def test_collectives_reads_tuples():
    lines = ["  %a = (f32[10]{0}, f32[]{:T(128)}) all-reduce(f32[10]{0} %p, "
             "f32[] %q), replica_groups={}, to_apply=%add",
             "  %b = f32[4]{0} fusion(f32[4] %all-reduce.9), calls=%f",
             "  %c = f32[8,85]{1,0} all-gather(f32[2,85]{1,0} %l), "
             "dimensions={0}",
             "  %d = f32[2] all-reduce-start(f32[2] %p), to_apply=%add"]
    assert collectives(lines) == [("all-reduce", 2), ("all-gather", 1),
                                  ("all-reduce-start", 1)]


# WADI's clients on the 2x2 host: 20 clients of 151,200 rows, 5 per chip
WADI_C, WADI_N, WADI_D = 20, 151200, 84


def _wadi_shards(four_chips):
    rows = NamedSharding(four_chips, P("data"))
    return (jax.ShapeDtypeStruct((WADI_C, WADI_N, WADI_D), F32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((WADI_C, WADI_N), F32, sharding=rows))


def _assert_per_chip(hlo: str):
    """Each chip holds its own 5 clients' rows; no program gathers the
    20 clients' rows onto one chip."""
    per = WADI_C // 4
    assert f"f32[{per},{WADI_N},{WADI_D}]" in hlo
    assert f"f32[{WADI_C},{WADI_N}" not in hlo


def test_sharded_init_compiles(four_chips, on_tpu):
    """DEM's fed-kmeans init on sharded clients, at WADI's per-chip
    shapes: the local k-means and its kernel run inside ``shard_map``
    (outside it the TPU compiler refuses to partition a Mosaic kernel),
    each Lloyd loop reads a slab built before it, one ``all_gather`` of
    the local centers and sizes is the init's only collective, and the
    data moments take one ``psum``."""
    data, mask = _wadi_shards(four_chips)
    replicated = NamedSharding(four_chips, P())
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=replicated)
    hlo = federated_kmeans_sharded.lower(
        key, data, mask, mesh=four_chips, k_global=K).compile().as_text()
    _assert_slab_built_once(hlo, WADI_N, WADI_D)
    _assert_per_chip(hlo)
    assert collectives(hlo.splitlines()) == [("all-gather", 1)]
    centers = jax.ShapeDtypeStruct((K, WADI_D), F32, sharding=replicated)
    hlo = init_from_means_sharded.lower(
        centers, data, mask, mesh=four_chips).compile().as_text()
    _assert_per_chip(hlo)
    assert [op for op, _ in collectives(hlo.splitlines())] == ["all-reduce"]


def test_sharded_round_loop_compiles(four_chips, on_tpu):
    """DEM's round loop over sharded clients, at WADI's per-chip shapes:
    each shard's slab is prepared once, before the loop, and a round's
    only collective is one tuple all-reduce of the statistics s0, s1, s2
    and the log-likelihood. (The weight sum, the same in every round,
    XLA reduces once, before the loop.)"""
    strategy = DEMStrategy(k=K)
    backend = ShardedClients(*_wadi_shards(four_chips), four_chips)
    hlo = _iterate_jit.lower(
        strategy, backend,
        _state0(strategy, WADI_D, NamedSharding(four_chips, P())),
        200).compile().as_text()
    _assert_slab_built_once(hlo, WADI_N, WADI_D)
    _assert_one_ragged_estep(hlo, WADI_C // 4)
    _assert_per_chip(hlo)
    assert collectives(loop_lines(hlo)) == [("all-reduce", 4)]
