"""Federations back to back over a device mesh: the ``fit_mesh`` kind.

The ``fit`` kind's window, answers and check (``bench/kinds/fit.py``,
loaded through ``lib.registry``), with the clients sharded. Set-up builds
a one-axis mesh over the cell's chips from the configuration's ``mesh``
(its ``axis`` and ``chips``), makes the client slab as ``fit`` does,
places its data and mask over the mesh once, ``clients_per_chip``
clients on each chip, and warms up ``repro.api.DEM(k, mesh=mesh)``. The
window then hands the facade the placed split on every fit, so nothing
is copied or compiled there.

Only DEM runs on a mesh through the facade: a mix with another
``strategy`` is refused.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from lib import datasets, registry
from lib.registry import BenchError

fit = registry.kind("fit")

window = fit.window
end_to_end = fit.end_to_end
counts = fit.counts
layer_context = fit.layer_context
check = fit.check


def mesh_of(cfg: dict):
    """The configuration's mesh: its ``chips`` devices on one axis."""
    m = cfg["mesh"]
    chips = int(m["chips"])
    if int(cfg["clients"]) != chips * int(m["clients_per_chip"]):
        raise BenchError(f"{cfg['clients']} clients are not "
                         f"{m['clients_per_chip']} on each of {chips} chips")
    # Auto axes: the answers are arrays like any other, so the check and
    # a caller's eager code need no mesh context
    return jax.make_mesh((chips,), (m["axis"],),
                         devices=jax.devices()[:chips],
                         axis_types=(AxisType.Auto,))


def setup(ctx) -> dict:
    from repro.api import DEM, FitConfig
    if ctx.traffic["strategy"] != "dem":
        raise BenchError(f"the fit_mesh kind runs DEM, not "
                         f"{ctx.traffic['strategy']!r}")
    mesh = mesh_of(ctx.cfg)
    runner = DEM(int(ctx.cfg["k"]),
                 config=FitConfig(**ctx.traffic["fit_config"]), mesh=mesh)
    work = int(ctx.traffic["work_seed"])
    split = fit.make_split(ctx, work)
    with ctx.spans.span("bench.place"):
        data, mask = jax.device_put(
            (split.data, split.mask),
            NamedSharding(mesh, P(ctx.cfg["mesh"]["axis"])))
        jax.block_until_ready((data, mask))
    split = split._replace(data=data, mask=mask)
    with ctx.spans.span("bench.warmup"):
        warm = runner.run(split, key=jax.random.fold_in(
            datasets.run_key(work), 0))
        jax.block_until_ready(warm.global_gmm)
    return {"split": split, "runner": runner,
            "keys": fit.fit_keys(work, int(ctx.traffic["key_cycle"]),
                                 ctx.seed)}


def release(ctx, state, rec) -> None:
    """``fit.release`` on a host copy of the rows: one copy of the sharded
    slab, not one gather per client."""
    split = state["split"]
    state["split"] = split._replace(data=np.asarray(split.data),
                                    mask=np.asarray(split.mask))
    fit.release(ctx, state, rec)
