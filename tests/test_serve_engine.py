"""The serving engine contract (DESIGN.md §10): continuous batching over
one compiled slab shape, scores within a few ulps of ``repro.api`` scoring,
and the drain-and-install hot swap — version flips at exactly one
boundary, no request dropped, every result tagged with the one model
that scored it. Plus the versioned checkpoint publish/subscribe seam the
swap rides on (atomicity by write-then-rename, bf16 round-trip, loader
errors that name the offending leaf).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GMMEstimator, Scorer, log_prob
from repro.checkpoint import (latest_version, load_checkpoint,
                              load_published, publish_checkpoint,
                              save_checkpoint)
from repro.core.gmm import GMM
from repro.serve import (ModelStore, ScoreConfig, ScoreRequest,
                         ScoringEngine, SlotPool)

DIM = 5

# Engine and repro.api run the same per-row math at different batch
# shapes, and XLA does not promise one float32 accumulation order for a
# dot product across shapes (the CPU backend was measured 4 ulps apart).
# 16 float32 ulps bounds that, relative and near zero.
ULP16 = 16 * 2.0 ** -23


@pytest.fixture(scope="module")
def fitted():
    """Two distinct fitted models over the same feature space — the
    swap's before/after pair — plus a held-out scoring stream."""
    rng = np.random.default_rng(3)
    x = np.concatenate([rng.normal(m, 1.0, (400, DIM))
                        for m in (0.0, 5.0, 9.0)]).astype(np.float32)
    gmm_a = GMMEstimator(k=3, seed=0).fit(x).gmm_
    gmm_b = GMMEstimator(k=3, seed=7).fit(x[::2] + 0.25).gmm_
    return gmm_a, gmm_b, x


def _requests(rng, sizes):
    return [ScoreRequest(i, rng.normal(2.0, 3.0, (n, DIM)))
            for i, n in enumerate(sizes)]


# ----------------------------------------------------------------------
# Correctness: engine scores == repro.api scores, to a few ulps
# ----------------------------------------------------------------------

class TestEngineScores:
    # 130/700 stream across micro-batches (> rows_per_slot), 64 fills a
    # slot exactly, 1 and 5 pad, 0 never occupies a slot.
    SIZES = (130, 5, 64, 700, 1, 0)

    def test_bit_identical_to_api_log_prob(self, fitted):
        gmm, _, _ = fitted
        reqs = _requests(np.random.default_rng(11), self.SIZES)
        eng = ScoringEngine(gmm, ScoreConfig(slots=3, rows_per_slot=64))
        got = {r.rid: r for r in eng.run(reqs)}
        assert len(got) == len(reqs)
        for req in reqs:
            res = got[req.rid]
            assert res.scores.shape == (req.num_rows,)
            assert res.scores.dtype == np.float32
            if req.num_rows:
                ref = np.asarray(log_prob(gmm, req.rows))
                np.testing.assert_allclose(res.scores, ref, rtol=ULP16,
                                           atol=ULP16)

    def test_slot_geometry_invariant(self, fitted):
        """Scores cannot depend on pool geometry: (3 slots x 64 rows)
        and (1 slot x 256 rows) produce identical bits."""
        gmm, _, _ = fitted
        reqs = _requests(np.random.default_rng(12), self.SIZES)
        a = {r.rid: r.scores for r in ScoringEngine(
            gmm, ScoreConfig(slots=3, rows_per_slot=64)).run(reqs)}
        b = {r.rid: r.scores for r in ScoringEngine(
            gmm, ScoreConfig(slots=1, rows_per_slot=256)).run(reqs)}
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])

    def test_anomaly_is_negated_log_prob(self, fitted):
        gmm, _, _ = fitted
        reqs = _requests(np.random.default_rng(13), (40, 3))
        eng = ScoringEngine(gmm, ScoreConfig(mode="anomaly", slots=2,
                                             rows_per_slot=32))
        for res in eng.run(reqs):
            ref = np.asarray(log_prob(gmm, reqs[res.rid].rows))
            np.testing.assert_allclose(res.scores, -ref, rtol=ULP16,
                                       atol=ULP16)

    def test_responsibilities_mode(self, fitted):
        gmm, _, _ = fitted
        reqs = _requests(np.random.default_rng(14), (70, 0, 9))
        eng = ScoringEngine(gmm, ScoreConfig(mode="responsibilities",
                                             slots=2, rows_per_slot=32))
        for res in eng.run(reqs):
            n = reqs[res.rid].num_rows
            assert res.scores.shape == (n, 3)
            if n:
                ref = np.asarray(
                    gmm.responsibilities(jnp.asarray(reqs[res.rid].rows)))
                np.testing.assert_allclose(res.scores, ref, atol=1e-6)
                np.testing.assert_allclose(res.scores.sum(axis=1), 1.0,
                                           atol=1e-5)

    def test_continuous_admission_mid_flight(self, fitted):
        """A request submitted while another streams through its slot is
        admitted into a free slot immediately — no lockstep waves."""
        gmm, _, _ = fitted
        eng = ScoringEngine(gmm, ScoreConfig(slots=2, rows_per_slot=16))
        rng = np.random.default_rng(15)
        long = ScoreRequest(0, rng.normal(size=(100, DIM)))  # 7 steps
        eng.submit(long)
        eng.step()
        late = ScoreRequest(1, rng.normal(size=(8, DIM)))
        eng.submit(late)
        finished = eng.step()  # late rides the free slot this very step
        assert [r.rid for r in finished] == [1]
        (rest,) = eng.drain()
        assert rest.rid == 0 and rest.scores.shape == (100,)

    def test_single_compile_across_admissions(self, fitted):
        """The hot path traces once per engine config — admitting,
        retiring and re-seeding requests never retraces."""
        gmm, _, _ = fitted
        cfg = ScoreConfig(slots=2, rows_per_slot=32)
        eng = ScoringEngine(gmm, cfg)
        reqs = _requests(np.random.default_rng(16), (100, 10, 33, 1))
        with jax.log_compiles():  # smoke: must not crash
            eng.run(reqs)
        from repro.serve.engine import _score_slab
        before = _score_slab._cache_size()
        eng.run(_requests(np.random.default_rng(17), (64, 2, 90)))
        assert _score_slab._cache_size() == before

    def test_submit_validates(self, fitted):
        gmm, _, _ = fitted
        eng = ScoringEngine(gmm)
        with pytest.raises(TypeError, match="ScoreRequest"):
            eng.submit(np.zeros((3, DIM)))
        with pytest.raises(ValueError, match="dim"):
            eng.submit(ScoreRequest(0, np.zeros((3, DIM + 1))))

    def test_config_validation(self):
        with pytest.raises(ValueError, match="mode"):
            ScoreConfig(mode="density")
        with pytest.raises(ValueError, match="backend"):
            ScoreConfig(backend="pallas")
        with pytest.raises(ValueError, match="slots"):
            ScoreConfig(slots=0)
        with pytest.raises(ValueError, match="rows must be"):
            ScoreRequest(0, np.zeros(DIM))


# ----------------------------------------------------------------------
# Hot swap: drain-and-install
# ----------------------------------------------------------------------

class TestHotSwap:
    def test_idle_swap_is_immediate(self, fitted):
        gmm_a, gmm_b, _ = fitted
        eng = ScoringEngine(gmm_a, version=1)
        eng.install(gmm_b, 2)
        assert eng.version == 2 and not eng.swap_pending
        assert eng.swaps == 1

    def test_swap_boundary_exact(self, fitted):
        """The full guarantee, mid-stream: every result is bit-identical
        to a fresh single-model engine holding its tagged version, the
        version tag flips at exactly one admission boundary, and no
        request is lost."""
        gmm_a, gmm_b, _ = fitted
        rng = np.random.default_rng(21)
        sizes = (50, 40, 33, 20, 10, 7, 64, 1)
        reqs = _requests(rng, sizes)
        cfg = ScoreConfig(slots=2, rows_per_slot=16)

        eng = ScoringEngine(gmm_a, cfg, version=1)
        for req in reqs[:4]:
            eng.submit(req)
        results = eng.step()          # slots busy, cursors mid-request
        eng.install(gmm_b, 2)         # swap lands mid-flight
        assert eng.swap_pending
        for req in reqs[4:]:
            eng.submit(req)           # queued behind the drain
        results += eng.drain()
        assert not eng.swap_pending and eng.version == 2
        assert eng.swaps == 1 and len(eng.swap_pauses) == 1

        # no request lost, each scored by exactly one model
        assert sorted(r.rid for r in results) == list(range(len(reqs)))
        by_rid = {r.rid: r for r in results}
        ref = {1: {r.rid: r.scores for r in ScoringEngine(
                   gmm_a, cfg, version=1).run(reqs)},
               2: {r.rid: r.scores for r in ScoringEngine(
                   gmm_b, cfg, version=2).run(reqs)}}
        for rid, res in by_rid.items():
            np.testing.assert_array_equal(
                res.scores, ref[res.model_version][rid])

        # the tag flips exactly once across the admission order (rids
        # were submitted in order and admission is FIFO)
        versions = [by_rid[rid].model_version for rid in range(len(reqs))]
        assert versions == sorted(versions)       # 1...1 then 2...2
        assert set(versions) == {1, 2}
        # exactly the requests ADMITTED before the install (the 2 slots'
        # occupants) stayed on the old model; the still-queued tail and
        # everything submitted later ride the new one
        assert versions[:2] == [1, 1] and versions[2:] == [2] * 6

    def test_admission_stalls_only_while_draining(self, fitted):
        gmm_a, gmm_b, _ = fitted
        eng = ScoringEngine(gmm_a, ScoreConfig(slots=1, rows_per_slot=8),
                            version=1)
        rng = np.random.default_rng(22)
        eng.submit(ScoreRequest(0, rng.normal(size=(24, DIM))))
        eng.step()
        eng.install(gmm_b, 2)
        eng.submit(ScoreRequest(1, rng.normal(size=(4, DIM))))
        stalled = eng.step()          # old request still draining
        assert [r.rid for r in stalled] == []
        assert eng.queued == 1 and eng.swap_pending
        rest = eng.drain()
        assert [r.model_version for r in rest] == [1, 2]
        assert eng.swap_pauses[0] >= 0.0

    def test_latest_wins_while_pending(self, fitted):
        gmm_a, gmm_b, _ = fitted
        eng = ScoringEngine(gmm_a, ScoreConfig(slots=1, rows_per_slot=4),
                            version=1)
        eng.submit(ScoreRequest(0, np.zeros((9, DIM), np.float32)))
        eng.step()
        eng.install(gmm_b, 2)
        eng.install(gmm_a, 3)         # replaces the pending install
        eng.drain()
        assert eng.version == 3 and eng.swaps == 1

    def test_swap_rejects_dim_change(self, fitted):
        gmm_a, _, _ = fitted
        other = GMM(jnp.ones(2) / 2, jnp.zeros((2, DIM + 1)),
                    jnp.ones((2, DIM + 1)))
        eng = ScoringEngine(gmm_a)
        with pytest.raises(ValueError, match="feature"):
            eng.install(other, 2)


# ----------------------------------------------------------------------
# ModelStore: versioned publish/subscribe
# ----------------------------------------------------------------------

class TestModelStore:
    def test_publish_poll_roundtrip(self, fitted, tmp_path):
        gmm_a, _, _ = fitted
        store = ModelStore(tmp_path)
        assert store.latest() is None and store.poll() is None
        v = store.publish(gmm_a, {"round": 0})
        assert v == 1 and store.latest_version() == 1
        published = store.poll()
        assert published.version == 1
        assert published.metadata["round"] == 0
        for got, want in zip(jax.tree_util.tree_leaves(published.gmm),
                             jax.tree_util.tree_leaves(gmm_a)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert store.poll() is None   # seen — fires once

    def test_poll_jumps_to_latest(self, fitted, tmp_path):
        gmm_a, gmm_b, _ = fitted
        store = ModelStore(tmp_path)
        store.publish(gmm_a)
        store.publish(gmm_b)
        store.publish(gmm_a)
        assert store.poll().version == 3  # intermediates skipped
        assert store.poll() is None

    def test_subscriber_cursors_are_independent(self, fitted, tmp_path):
        gmm_a, _, _ = fitted
        pub, sub = ModelStore(tmp_path), ModelStore(tmp_path)
        pub.publish(gmm_a)
        assert pub.poll() is not None
        assert sub.poll() is not None  # its own cursor

    def test_load_errors(self, fitted, tmp_path):
        gmm_a, _, _ = fitted
        store = ModelStore(tmp_path)
        with pytest.raises(FileNotFoundError):
            store.load(None)
        store.publish(gmm_a)
        with pytest.raises(ValueError, match="never published"):
            store.load(5)
        with pytest.raises(TypeError, match="GMM"):
            store.publish(np.zeros(3))

    def test_engine_follows_store(self, fitted, tmp_path):
        """End to end: publish round 1, serve, publish round 2 mid-stream
        — the engine hot-swaps in and tags results correctly."""
        gmm_a, gmm_b, _ = fitted
        store = ModelStore(tmp_path)
        store.publish(gmm_a)
        eng = ScoringEngine.from_store(
            ModelStore(tmp_path), ScoreConfig(slots=1, rows_per_slot=8))
        assert eng.version == 1
        rng = np.random.default_rng(31)
        rows0 = rng.normal(size=(20, DIM)).astype(np.float32)
        rows1 = rng.normal(size=(4, DIM)).astype(np.float32)
        eng.submit(ScoreRequest(0, rows0))
        eng.step()
        store.publish(gmm_b)          # a new round lands mid-request
        eng.submit(ScoreRequest(1, rows1))
        results = {r.rid: r for r in eng.drain()}
        assert results[0].model_version == 1
        assert results[1].model_version == 2
        np.testing.assert_array_equal(results[0].scores,
                                      np.asarray(log_prob(gmm_a, rows0)))
        np.testing.assert_array_equal(results[1].scores,
                                      np.asarray(log_prob(gmm_b, rows1)))

    def test_from_store_empty_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="no published"):
            ScoringEngine.from_store(ModelStore(tmp_path))


# ----------------------------------------------------------------------
# Scorer facade
# ----------------------------------------------------------------------

class TestScorerFacade:
    def test_from_checkpoint_and_follow(self, fitted, tmp_path):
        gmm_a, gmm_b, x = fitted
        store = ModelStore(tmp_path)
        store.publish(gmm_a)
        scorer = Scorer.from_checkpoint(tmp_path, "anomaly", slots=2)
        assert scorer.model_version == 1
        got = scorer.score(x[:33])
        np.testing.assert_array_equal(got, -np.asarray(log_prob(gmm_a,
                                                                x[:33])))
        store.publish(gmm_b)          # next batch served by round 2
        got2 = scorer.score(x[:33])
        assert scorer.model_version == 2
        np.testing.assert_array_equal(got2, -np.asarray(log_prob(gmm_b,
                                                                 x[:33])))

    def test_pinned_version_never_follows(self, fitted, tmp_path):
        gmm_a, gmm_b, x = fitted
        store = ModelStore(tmp_path)
        store.publish(gmm_a)
        store.publish(gmm_b)
        scorer = Scorer.from_checkpoint(tmp_path, version=1)
        store.publish(gmm_b)
        scorer.score(x[:5])
        assert scorer.model_version == 1

    def test_empty_store_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="no published"):
            Scorer.from_checkpoint(tmp_path)


# ----------------------------------------------------------------------
# Checkpoint store: loader errors + dtype round-trip + atomicity
# ----------------------------------------------------------------------

class TestCheckpointStore:
    def test_missing_leaf_names_key(self, tmp_path):
        tree = {"w": jnp.ones(3), "mu": jnp.zeros((3, 2))}
        path = tmp_path / "ckpt"
        save_checkpoint(path, {"w": tree["w"]})
        with pytest.raises(ValueError, match=r"missing pytree leaf 'mu'"):
            load_checkpoint(path, tree)

    def test_shape_mismatch_names_key(self, tmp_path):
        path = tmp_path / "ckpt"
        save_checkpoint(path, {"w": jnp.ones(3)})
        with pytest.raises(ValueError,
                           match=r"leaf 'w' has shape \(3,\)"):
            load_checkpoint(path, {"w": jnp.ones(4)})

    def test_bf16_roundtrip_exact(self, tmp_path):
        """bf16 -> f32 npz -> bf16 is exact (f32 holds every bf16 value),
        and the restored leaf keeps the template dtype."""
        rng = np.random.default_rng(5)
        w = jnp.asarray(rng.normal(0, 3, (4, 7)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        path = tmp_path / "ckpt"
        save_checkpoint(path, {"w": w})
        restored, _ = load_checkpoint(path, {"w": jnp.zeros((4, 7),
                                                            jnp.bfloat16)})
        assert restored["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                      np.asarray(w, np.float32))

    def test_publish_is_versioned_and_atomic(self, fitted, tmp_path):
        gmm_a, _, _ = fitted
        assert latest_version(tmp_path) is None
        v1 = publish_checkpoint(tmp_path, gmm_a, {"round": 1})
        v2 = publish_checkpoint(tmp_path, gmm_a, {"round": 2})
        assert (v1, v2) == (1, 2)
        # no tmp litter: the write-then-rename protocol leaves only the
        # published artifacts
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["LATEST", "model-000001.json", "model-000001.npz",
                         "model-000002.json", "model-000002.npz"]
        gmm, meta, v = load_published(tmp_path, gmm_a)
        assert v == 2 and meta["round"] == 2 and meta["version"] == 2
        assert set(meta["leaves"]) == {"0", "1", "2"}
        with pytest.raises(ValueError, match="never published"):
            load_published(tmp_path, gmm_a, version=9)

    def test_publish_survives_stale_latest(self, fitted, tmp_path):
        """A torn LATEST pointer (crash between renames) must not wedge
        the stream: the next publish scans and moves past it."""
        gmm_a, _, _ = fitted
        publish_checkpoint(tmp_path, gmm_a)
        os.remove(tmp_path / "LATEST")
        v = publish_checkpoint(tmp_path, gmm_a)
        assert v == 2
        assert json.loads((tmp_path / "LATEST").read_text())["version"] == 2


# ----------------------------------------------------------------------
# SlotPool bookkeeping
# ----------------------------------------------------------------------

class TestSlotPool:
    def test_admit_overflow_raises(self):
        pool = SlotPool(1, 4, DIM)
        from repro.serve.slots import InFlight
        pool.admit(InFlight(ScoreRequest(0, np.zeros((2, DIM))), 0.0, 1))
        assert pool.free == 0
        with pytest.raises(RuntimeError, match="full"):
            pool.admit(InFlight(ScoreRequest(1, np.zeros((2, DIM))),
                                0.0, 1))

    def test_geometry_validation(self):
        with pytest.raises(ValueError, match="positive"):
            SlotPool(0, 4, DIM)
