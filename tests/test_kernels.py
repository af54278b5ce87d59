"""Pallas kernel validation: interpret-mode vs pure-jnp oracles across a
shape/dtype sweep (per-kernel allclose, as required)."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.kernels import ops, ref

SHAPES = [  # (N, d, K)
    (64, 4, 2),
    (256, 24, 30),       # the paper's MNIST setting
    (1000, 11, 15),      # VEHICLE
    (513, 84, 10),       # WADI, non-aligned N
    (100, 38, 10),       # SMD
    (2048, 128, 64),     # aligned everything
    (17, 3, 1),          # degenerate small
]


def make_inputs(rng, n, d, k, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(0, 2, (n, d)), dtype)
    mu = jnp.asarray(rng.normal(0, 2, (k, d)), jnp.float32)
    var = jnp.asarray(rng.uniform(0.05, 3.0, (k, d)), jnp.float32)
    lw = jnp.asarray(np.log(rng.dirichlet(np.ones(k))), jnp.float32)
    return x, mu, var, lw


class TestGMMLogpdf:
    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_matches_ref(self, n, d, k):
        rng = np.random.default_rng(n * 31 + d * 7 + k)
        x, mu, var, lw = make_inputs(rng, n, d, k)
        out = ops.gmm_logpdf(x, mu, var, lw, interpret=True)
        exp = ref.gmm_logpdf_ref(x, mu, var, lw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)

    def test_no_log_weights(self):
        rng = np.random.default_rng(0)
        x, mu, var, _ = make_inputs(rng, 100, 8, 4)
        out = ops.gmm_logpdf(x, mu, var, None, interpret=True)
        exp = ref.gmm_logpdf_ref(x, mu, var, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-4, atol=2e-4)

    def test_bfloat16_input(self):
        rng = np.random.default_rng(1)
        x, mu, var, lw = make_inputs(rng, 128, 16, 8, dtype=jnp.bfloat16)
        out = ops.gmm_logpdf(x, mu, var, lw, interpret=True)
        exp = ref.gmm_logpdf_ref(x.astype(jnp.float32), mu, var, lw)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=0.05, atol=0.3)

    def test_block_shape_invariance(self):
        rng = np.random.default_rng(2)
        x, mu, var, lw = make_inputs(rng, 512, 24, 30)
        a = ops.gmm_logpdf(x, mu, var, lw, block_n=128, block_k=128,
                           interpret=True)
        b = ops.gmm_logpdf(x, mu, var, lw, block_n=512, block_k=256,
                           interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


class TestEstepStats:
    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_matches_ref(self, n, d, k):
        rng = np.random.default_rng(n * 13 + d + k)
        x, mu, var, lw = make_inputs(rng, n, d, k)
        w = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
        s0, s1, s2, ll = ops.estep_stats(x, mu, var, lw, w, interpret=True)
        e0, e1, e2, el = ref.estep_stats_ref(x, mu, var, lw, w)
        np.testing.assert_allclose(np.asarray(s0), np.asarray(e0), rtol=1e-3,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(e1), rtol=1e-3,
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(e2), rtol=1e-3,
                                   atol=1e-3)
        np.testing.assert_allclose(float(ll), float(el), rtol=1e-4)

    def test_unit_weights_default(self):
        rng = np.random.default_rng(3)
        x, mu, var, lw = make_inputs(rng, 200, 10, 5)
        s0, *_ = ops.estep_stats(x, mu, var, lw, None, interpret=True)
        np.testing.assert_allclose(float(jnp.sum(s0)), 200.0, rtol=1e-4)

    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_prepared_slab_is_bit_identical(self, n, d, k):
        """A slab prepared once gives the kernel the bytes a per-call pad
        gives it: the same statistics, bit for bit."""
        rng = np.random.default_rng(n * 5 + d + k)
        x, mu, var, lw = make_inputs(rng, n, d, k)
        w = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
        per_call = ops.estep_stats(x, mu, var, lw, w, interpret=True)
        slab = ops.estep_stats(ops.prepare(x, w), mu, var, lw,
                               interpret=True)
        for a, b in zip(per_call, slab):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_slab_carries_its_weights(self):
        rng = np.random.default_rng(5)
        x, mu, var, lw = make_inputs(rng, 100, 8, 4)
        slab = ops.prepare(x)
        assert slab.x.shape == (512, 128) and slab.w.shape == (512, 1)
        assert (slab.n, slab.d) == (100, 8)
        np.testing.assert_array_equal(np.asarray(slab.w[:, 0]),
                                      np.r_[np.ones(100), np.zeros(412)])
        with pytest.raises(ValueError, match="carries its weights"):
            ops.estep_stats(slab, mu, var, lw, jnp.ones(100),
                            interpret=True)
        assert ops.slab_bytes(100, 8) == 512 * 256 * 4
        assert ops.slab_bytes(100, 8, weights=False) == 512 * 128 * 4

    def test_multi_block_accumulation(self):
        """Accumulation across sequential grid steps must equal single block."""
        rng = np.random.default_rng(4)
        x, mu, var, lw = make_inputs(rng, 2048, 16, 8)
        a = ops.estep_stats(x, mu, var, lw, block_n=256, interpret=True)
        b = ops.estep_stats(x, mu, var, lw, block_n=2048, interpret=True)
        for u, v in zip(a, b):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=1e-4, atol=1e-3)


BN = ops.BLOCK_N
N_PAD = 3 * BN


def _full_grid(slab):
    """``slab`` with every row block counted: the kernel's full grid."""
    full = jnp.full_like(slab.blocks, slab.x.shape[-2] // slab.block_n)
    return dataclasses.replace(slab, blocks=full)


def _stats(slab, mu, var, lw):
    return ops.estep_stats(slab, mu, var, lw, interpret=True)


def _assert_equal(got, want):
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_close(got, want):
    """Equal up to float32 rounding: a call compiled alone may order its
    sums apart from the same call inside a batched program."""
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)


def _ragged(rng, sizes, d=84, k=10):
    """Clients of ``sizes`` real rows padded to ``N_PAD``, the way a
    client split pads them: zero rows of zero weight."""
    x, mu, var, lw = make_inputs(rng, N_PAD, d, k)
    rows = jnp.arange(N_PAD)
    xs = jnp.stack([jnp.where((rows < n)[:, None], x, 0.0) for n in sizes])
    ws = jnp.stack([(rows < n).astype(jnp.float32) for n in sizes])
    return xs, ws, mu, var, lw


class TestRaggedGrid:
    """The E-step computes each client's row blocks up to its last
    weighted row and skips the rest, which would add exactly zero: the
    statistics equal the full grid's bit for bit."""

    @pytest.mark.parametrize("n", [0, 1, BN - 1, BN, BN + 1, N_PAD])
    def test_one_client_equals_full_grid(self, n):
        rng = np.random.default_rng(n)
        xs, ws, mu, var, lw = _ragged(rng, [n])
        slab = ops.prepare(xs[0], ws[0])
        assert int(slab.blocks) == -(-n // BN)
        _assert_equal(_stats(slab, mu, var, lw),
                      _stats(_full_grid(slab), mu, var, lw))
        if n == 0:
            assert all(not np.any(np.asarray(v))
                       for v in _stats(slab, mu, var, lw))

    def test_prepare_counts_blocks_to_the_last_weighted_row(self):
        x = jnp.ones((1200, 3))
        assert int(ops.prepare(x).blocks) == 3
        assert int(ops.prepare(x, jnp.zeros(1200)).blocks) == 0
        w = jnp.zeros(1200).at[BN].set(0.5)
        assert int(ops.prepare(x, w).blocks) == 2
        assert int(ops.prepare(x, w, block_n=256).blocks) == 3
        assert ops.prepare(x).blocks.dtype == jnp.int32
        slab = jax.vmap(ops.prepare)(jnp.ones((3, 1200, 3)),
                                     jnp.stack([jnp.zeros(1200), w,
                                                jnp.ones(1200)]))
        np.testing.assert_array_equal(np.asarray(slab.blocks), [0, 2, 3])

    def test_zero_weight_block_before_the_last_row_is_computed(self):
        rng = np.random.default_rng(7)
        xs, ws, mu, var, lw = _ragged(rng, [N_PAD])
        w = ws[0].at[:BN].set(0.0).at[BN + 10:].set(0.0)
        slab = ops.prepare(xs[0], w)
        assert int(slab.blocks) == 2
        got = _stats(slab, mu, var, lw)
        _assert_equal(got, _stats(_full_grid(slab), mu, var, lw))
        _assert_equal(got, _stats(ops.prepare(xs[0, BN:BN + 10]), mu, var,
                                  lw)[:1] + got[1:])

    def test_vmapped_mixed_sizes(self):
        sizes = [0, 1, BN - 1, BN, BN + 1, N_PAD]
        xs, ws, mu, var, lw = _ragged(np.random.default_rng(11), sizes)
        stats = jax.jit(jax.vmap(lambda s: _stats(s, mu, var, lw)))
        slabs = jax.vmap(ops.prepare)(xs, ws)
        got = stats(slabs)
        _assert_equal(got, stats(_full_grid(slabs)))
        for c, n in enumerate(sizes):
            one = _stats(ops.prepare(xs[c], ws[c]), mu, var, lw)
            _assert_close([v[c] for v in got], one)
        # one kernel over every client, never a loop of per-client calls
        text = str(jax.make_jaxpr(jax.vmap(
            lambda s: _stats(s, mu, var, lw)))(slabs))
        assert text.count("pallas_call") == 1 and "while" not in text

    def test_vmapped_while_loop_stops_clients_apart(self):
        sizes = [BN - 1, N_PAD, 0, BN + 1]
        xs, ws, _, var, lw = _ragged(np.random.default_rng(12), sizes, d=5,
                                     k=3)
        means = jnp.asarray(np.random.default_rng(13).normal(
            size=(len(sizes), 3, 5)), jnp.float32)
        stop = jnp.asarray([1, 4, 2, 3])

        def fit(x, w, mu, stop, full):
            slab = ops.prepare(x, w)
            slab = _full_grid(slab) if full else slab

            def body(carry):
                it, mu, ll = carry
                s0, s1, _, l = _stats(slab, mu, var, lw)
                return it + 1, s1 / jnp.maximum(s0, 1.0)[:, None], ll + l

            return jax.lax.while_loop(lambda c: c[0] < stop, body,
                                      (0, mu, jnp.float32(0)))

        run = jax.jit(jax.vmap(fit, in_axes=(0, 0, 0, 0, None)),
                      static_argnums=4)
        got = run(xs, ws, means, stop, False)
        _assert_equal(got, run(xs, ws, means, stop, True))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(stop))
        for c in range(len(sizes)):
            _assert_close([v[c] for v in got],
                          fit(xs[c], ws[c], means[c], stop[c], False))

    def test_nested_vmap_folds_into_one_client_axis(self):
        sizes = [1, N_PAD, BN, 0, BN + 1, 2 * BN - 1]
        xs, ws, mu, var, lw = _ragged(np.random.default_rng(14), sizes)
        slabs = jax.vmap(ops.prepare)(xs, ws)
        nested = jax.tree.map(lambda v: v.reshape(2, 3, *v.shape[1:]),
                              slabs)
        one = lambda s: _stats(s, mu, var, lw)
        run = jax.jit(jax.vmap(jax.vmap(one)))
        got = run(nested)
        _assert_equal(got, run(_full_grid(nested)))
        _assert_close([v.reshape(6, *v.shape[2:]) for v in got],
                      jax.jit(jax.vmap(one))(slabs))
        text = str(jax.make_jaxpr(jax.vmap(jax.vmap(one)))(nested))
        assert text.count("pallas_call") == 1

    def test_slab_is_a_pytree_through_jit_and_vmap(self):
        x, w = jnp.ones((2, 700, 3)), jnp.ones((2, 700))
        slab = jax.jit(jax.vmap(ops.prepare))(x, w)
        assert slab.blocks.shape == (2,) and slab.x.shape == (2, BN * 2, 128)
        assert (slab.n, slab.d, slab.block_n) == (700, 3, BN)
        leaves, tree = jax.tree.flatten(slab)
        assert len(leaves) == 3
        assert jax.tree.unflatten(tree, leaves) == slab

    def test_slab_passes_shard_map(self):
        """Each of 4 devices prepares its clients' slabs and runs the
        vmapped E-step inside ``shard_map``: the psum of the shards equals
        one device's sum over every client."""
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=4"
            import json
            import jax, jax.numpy as jnp
            import numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.kernels import ops

            rng = np.random.default_rng(0)
            c, n, d, k = 8, 1300, 5, 3
            rows = np.arange(n)
            sizes = [0, 1, 511, 512, 513, 1300, 700, 40]
            x = jnp.asarray(rng.normal(size=(c, n, d)), jnp.float32)
            w = jnp.asarray(np.stack([rows < s for s in sizes]), jnp.float32)
            mu = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
            var, lw = jnp.ones((k, d)), jnp.log(jnp.ones(k) / k)
            step = lambda s: ops.estep_stats(s, mu, var, lw, interpret=True)

            def shard(x, w):
                slabs = jax.vmap(ops.prepare)(x, w)
                stats = jax.vmap(step)(slabs)
                return slabs.blocks, jax.tree.map(
                    lambda v: jax.lax.psum(jnp.sum(v, 0), "data"), stats)

            mesh = jax.make_mesh((4,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            blocks, got = jax.jit(jax.shard_map(
                shard, mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=(P("data"), P()), check_vma=False))(x, w)
            want = jax.tree.map(lambda v: jnp.sum(v, 0), jax.jit(jax.vmap(
                step))(jax.vmap(ops.prepare)(x, w)))
            print(json.dumps({
                "devices": len(jax.devices()),
                "blocks": np.asarray(blocks).tolist(),
                "close": all(bool(np.allclose(a, b, rtol=1e-5, atol=1e-4))
                             for a, b in zip(got, want))}))
        """)
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=600,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert res.returncode == 0, res.stderr[-3000:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert out == {"devices": 4, "blocks": [0, 1, 1, 1, 2, 3, 2, 1],
                       "close": True}


class TestKmeansAssign:
    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_matches_ref(self, n, d, k):
        rng = np.random.default_rng(n + d * 3 + k * 11)
        x, mu, _, _ = make_inputs(rng, n, d, k)
        ia, da = ops.kmeans_assign(x, mu, interpret=True)
        ie, de = ref.kmeans_assign_ref(x, mu)
        assert bool(jnp.all(ia == ie))
        np.testing.assert_allclose(np.asarray(da), np.asarray(de), rtol=1e-4,
                                   atol=1e-4)

    @pytest.mark.parametrize("n,d,k", SHAPES)
    def test_prepared_slab_is_bit_identical(self, n, d, k):
        rng = np.random.default_rng(n * 7 + d + k)
        x, mu, _, _ = make_inputs(rng, n, d, k)
        per_call = ops.kmeans_assign(x, mu, interpret=True)
        slab = ops.kmeans_assign(ops.prepare(x), mu, interpret=True)
        for a, b in zip(per_call, slab):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=15, deadline=None)
@given(n=hst.integers(1, 300), d=hst.integers(1, 40), k=hst.integers(1, 33),
       seed=hst.integers(0, 10**5))
def test_logpdf_property_sweep(n, d, k, seed):
    rng = np.random.default_rng(seed)
    x, mu, var, lw = make_inputs(rng, n, d, k)
    out = ops.gmm_logpdf(x, mu, var, lw, interpret=True)
    exp = ref.gmm_logpdf_ref(x, mu, var, lw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-3,
                               atol=1e-3)
