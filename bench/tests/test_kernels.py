"""Kernel work counts against hand-worked shapes."""
import numpy as np
import pytest

from lib import registry


def test_estep_stats_count_by_hand():
    kc = registry.kernel_count("estep_stats")
    # 1,000 rows, d = 84, K = 10: 8 * 1000 * 84 * 10 flops;
    # (1000 * 84 + 1000) float32 words read
    assert kc.count(1000, 84, 10) == (6_720_000.0, 340_000.0)


def test_gmm_logpdf_count_by_hand():
    kc = registry.kernel_count("gmm_logpdf")
    # 4,096 rows, d = 84, K = 10: 4 * 4096 * 84 * 10 flops;
    # rows read (4096 * 84) and log densities written (4096 * 10), 4 B each
    assert kc.count(4096, 84, 10) == (13_762_560.0, 1_540_096.0)


@pytest.mark.parametrize("pad_rows,lanes", [(0, 84), (1000, 128)])
def test_cell_work_ignores_padding(pad_rows, lanes):
    """The E-step work a fit cell hands its roofline reader comes from the
    clients' useful rows, the E-steps each fit reports, and the unpadded d
    and K: padding the slab's rows or its lanes changes nothing."""
    import jax.numpy as jnp
    from types import SimpleNamespace
    kind = registry.kind("fit")
    sizes = np.array([700, 300, 500])
    data = jnp.zeros((3, 700 + pad_rows, lanes))
    split = SimpleNamespace(data=data, sizes=sizes)
    ctx = SimpleNamespace(cfg={"k": 10, "d": 84})
    dem = SimpleNamespace(n_rounds=4)
    fedgen = SimpleNamespace(local_results=[
        SimpleNamespace(n_iter=n) for n in (5, 9, 2)])
    fits = [{"estep_rows": kind.estep_rows(dem, sizes)},
            {"estep_rows": kind.estep_rows(fedgen, sizes)}]
    layer = kind.layer_context(ctx, {"split": split}, {"fits": fits})
    assert fits[0]["estep_rows"] == 4 * 1500
    assert fits[1]["estep_rows"] == 5 * 700 + 9 * 300 + 2 * 500
    assert layer["estep_rows"] == 6000 + 7200
    assert (layer["d"], layer["k"]) == (84, 10)


def test_counts_scale_linearly_in_rows():
    for kernel in ("estep_stats", "gmm_logpdf"):
        kc = registry.kernel_count(kernel)
        f1, b1 = kc.count(500, 38, 10)
        f2, b2 = kc.count(1000, 38, 10)
        assert (f2, b2) == (2 * f1, 2 * b1)
