"""The federation runtime: one round driver under every federated
algorithm in the repo (DESIGN.md §9).

Tian et al.'s federated EM, Garst et al.'s federated k-means, the paper's
one-shot FedGenGMM and the DEM baseline all decompose into the same
round::

    client-update  ->  uplink  ->  server-combine  ->  broadcast

so this module owns that shape exactly once. A
:class:`FederationStrategy` supplies the algorithm (``local_step`` /
``server_combine`` / ``converged`` / ``round_payload``); a client
*backend* supplies where the clients live (a padded resident
:class:`~repro.core.partition.ClientSplit`, a list of out-of-core
:class:`~repro.data.sources.DataSource` streams, or shards of a device
mesh); and :func:`run_rounds` is the single driver that owns the round
loop, the input-type dispatch, and the communication ledger
(``repro.fed.ledger``). The algorithms in ``repro.core.fedgen`` /
``repro.core.dem`` and the new FedEM / FedKMeans baselines
(``repro.fed.strategies``) are all strategy definitions on this
substrate — none of them carries its own client loop any more.

Execution modes (picked per backend, never per strategy):

- resident clients (split or sharded mesh): the whole round loop runs as
  ONE jitted ``lax.while_loop`` — structurally identical to the
  pre-refactor ``_dem_loop``/``dem_sharded`` loops, which is what keeps
  the re-landed algorithms bit-identical to their history;
- source clients: a host-side round loop (a ``DataSource`` cannot live
  inside jit) with the same state transitions, mirroring the engine's
  ``host_em_loop`` semantics (Python-float convergence arithmetic).

The phases of ``run_rounds`` are ``repro.rounds.*`` profiler spans, and
a round's phases inside the jitted loop are named scopes (DESIGN.md
§13).

This module deliberately imports nothing from ``repro.core`` at module
top (only ``repro.data.sources``, which is itself repro-free), so
``core/fedgen.py`` and ``core/dem.py`` can import the runtime without
cycles; the one :class:`ClientSplit` isinstance check is a call-time
import.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.sources import DataSource
from repro.fed.ledger import CommStats, RoundPayload


@runtime_checkable
class FederationStrategy(Protocol):
    """The round-based strategy contract (duck-typed; subclassing is not
    required — frozen dataclasses are the idiom, so a strategy can ride
    through jit as a static argument).

    Iterative strategies implement:

    - ``init_state(key, backend) -> state`` — host-side; build round-0
      state (global model, convergence scalars). Numeric knobs that must
      not recompile the loop when swept (tol, reg_covar) belong in the
      *state* (traced), not in strategy fields (static).
    - ``local_step(state, x, w, idx) -> payload`` — ONE client's update:
      an additive pytree (the uplink). Must be traceable; ``x`` is that
      client's rows (array or DataSource), ``w`` its padding mask (None
      for sources), ``idx`` its global client index.
    - ``server_combine(state, total) -> state`` — the server side of the
      round, from the client-summed payload.
    - ``converged(state) -> bool`` — jnp bool under jit, Python bool on
      the host path (state scalars differ accordingly).
    - ``keep_going(state) -> bool`` (optional) — the loop-continuation
      predicate when it is NOT simply ``not converged``. The historical
      EM loops continue on ``delta > tol`` and report convergence as
      ``delta <= tol`` — with a NaN convergence scalar BOTH are false, so
      a degenerate run stops after one more round AND reports
      not-converged instead of spinning to the round budget. Strategies
      with that semantics implement both predicates; the driver falls
      back to ``not converged`` when ``keep_going`` is absent.
    - ``lanes_computed(d) -> int`` (optional) — the feature width
      ``local_step`` computes over (d, or d padded by a kernel), for the
      round loop's profiler counters; left out of them when absent.
    - ``row_block() -> int | None`` (optional) — the rows of the blocks
      ``local_step``'s kernel computes up to a client's last weighted
      row, skipping the rest of its padded rows, for the round loop's
      ``rows_computed`` counter; absent or None, every padded row counts.
    - ``prepare_client(x, w)`` (optional) — one resident client's rows as
      ``local_step`` reads them, built once before the jitted round loop
      (the fused kernel's padded slab, or ``x`` itself); absent, or on
      source clients, ``local_step`` gets the rows.
    - ``prepared_bytes(backend, phase) -> int | None`` (optional) — the
      device bytes of padded kernel operands the ``"init"`` or
      ``"loop"`` phase builds once (one chip's, on sharded clients), for
      that phase's profiler span; left out of it when absent or None.
    - ``gathered_bytes(backend) -> int | None`` (optional) — the bytes
      the init's ``all_gather`` carries on sharded clients, for the
      ``repro.rounds.init`` span; left out of it when absent or None.
    - ``round_payload(backend, state) -> RoundPayload`` — what one round
      moves; the driver multiplies by the realized round count.
    - ``finalize(state, n_rounds, converged, comm) -> result``.

    One-shot strategies (``one_shot = True``) implement ``run_once(state,
    backend) -> state`` instead of ``local_step``/``server_combine``/
    ``converged``: the single round runs host-side (FedGenGMM's local
    fits include Python-level per-client BIC selection).
    """

    one_shot: bool

    def init_state(self, key: jax.Array, backend) -> Any: ...

    def round_payload(self, backend, state) -> RoundPayload: ...

    def finalize(self, state, n_rounds, converged, comm: CommStats): ...


# ----------------------------------------------------------------------
# Client backends: where the clients live
# ----------------------------------------------------------------------
# Each backend exposes the same two faces:
#   - host metadata (kind / num_clients / dim / sizes / the original
#     container) that strategies use in init_state and accounting;
#   - reduce_clients(local_step, state, cohort=None, weights=None): sum
#     the per-client payload pytrees — a vmap + tree-sum (split), a
#     Python loop (sources), or a shard_map + psum (mesh). With a
#     ``cohort`` (sorted (m,) global client indices from the driver's
#     sampler) only the sampled clients compute: the split backend
#     gathers the (m, N, d) cohort slab and vmaps over m (indices are
#     TRACED, so membership changes never retrace; m is static, so one
#     compiled shape serves every round), the source backend iterates
#     only the cohort's streams, and the sharded backend gathers
#     per-shard and psums the realized contributors. ``weights`` (0/1
#     per cohort member, from the driver's straggler policy) zero out
#     clients that missed the round deadline. The jittable backends are
#     pytrees so the driver can pass them through the jitted round loop.


def _weight_bcast(w, s):
    """Reshape per-client weights (m,) to broadcast against a stacked
    per-client payload leaf (m, ...)."""
    return w.reshape(w.shape + (1,) * (s.ndim - 1)).astype(s.dtype)


def _wrap_step(local_step, state, transform, tparams, tkey, members):
    """Per-client step with the uplink transform (§11) applied between
    ``local_step`` and the reduce. Every client's ``apply`` receives the
    SAME round key (``fold_in(key(seed), round)``) — identically on every
    backend — and derives its own streams from it: value-level transforms
    fold in the client index (split and source runs draw the same
    per-client noise), pairwise masking folds in the sorted pair (both
    endpoints of a pair must derive the SAME stream, which is exactly why
    the driver must not pre-fold the client index here). With no
    transform this is exactly the historical step (bit-identity
    preserved)."""
    if transform is None:
        return lambda x, w, i: local_step(state, x, w, i)

    def step(x, w, i):
        payload = local_step(state, x, w, i)
        return transform.apply(tkey, tparams, payload, i, members)

    return step


@jax.tree_util.register_pytree_node_class
class SplitClients:
    """Resident padded clients: ``data (C, N, d)``, ``mask (C, N)``.
    Each client's step reads its rows of ``data``, or of ``rows``, the
    strategy's ``prepare_client`` of them stacked over clients, once
    :meth:`prepared` has built it."""

    kind = "split"
    host = False

    def __init__(self, data: jax.Array, mask: jax.Array, split=None,
                 rows=None):
        self.data = data
        self.mask = mask
        self.split = split  # the original ClientSplit (host metadata)
        self.rows = rows

    def tree_flatten(self):
        return (self.data, self.mask, self.rows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, mask, rows = children
        return cls(data, mask, rows=rows)

    def prepared(self, prepare_client) -> "SplitClients":
        """These clients with ``prepare_client(x, w)`` of every client's
        rows as the rows its step reads, built once."""
        return SplitClients(self.data, self.mask, self.split,
                            jax.vmap(prepare_client)(self.data, self.mask))

    @property
    def num_clients(self) -> int:
        return self.data.shape[0]

    @property
    def dim(self) -> int:
        return self.data.shape[-1]

    @property
    def sizes(self):
        return self.split.sizes if self.split is not None else jnp.sum(
            self.mask, axis=1)

    @property
    def population_clients(self) -> int:
        return self.num_clients

    def reduce_clients(self, local_step, state, cohort=None, weights=None,
                       transform=None, tparams=None, tkey=None):
        """Vmap the per-client step over the (cohort) slab, apply the
        uplink ``transform`` (if any) per client, and tree-sum."""
        c = self.data.shape[0]
        members = jnp.arange(c) if cohort is None else cohort
        step = _wrap_step(local_step, state, transform, tparams, tkey,
                          members)
        rows = self.data if self.rows is None else self.rows
        if cohort is None:
            per = jax.vmap(step)(rows, self.mask, members)
            if weights is not None:
                per = jax.tree.map(
                    lambda s: s * _weight_bcast(weights, s), per)
            return jax.tree.map(lambda s: jnp.sum(s, axis=0), per)
        # Cohort execution: gather the (m, N, d) slab and compute ONLY
        # the sampled clients. The indices are traced (no retrace when
        # membership changes) and m is static (one compiled shape for
        # all rounds).
        per = jax.vmap(step)(
            jax.tree.map(lambda r: jnp.take(r, cohort, axis=0), rows),
            jnp.take(self.mask, cohort, axis=0), cohort)
        if weights is not None:
            per = jax.tree.map(lambda s: s * _weight_bcast(weights, s), per)
        # Scatter the m payloads into their population slots and reduce
        # over all C: same summation tree as the historical train-all +
        # zero-mask reduction, which is what keeps cyclic-cohort FedEM
        # bit-identical to its PR-6 self (f32 addition is order-
        # sensitive; a direct sum over m would round differently).
        return jax.tree.map(
            lambda s: jnp.sum(
                jnp.zeros((c,) + s.shape[1:], s.dtype).at[cohort].set(s),
                axis=0),
            per)


class SourceClients:
    """Out-of-core clients: one :class:`DataSource` stream each. Rounds
    run host-side (a source cannot live inside jit); per-client block
    loops stay jitted inside the engine.

    ``executor`` (a :class:`repro.fed.async_runtime.ClientExecutor`, or
    anything with ``map_ordered(fn, items) -> list``) overlaps the
    per-client steps: each cohort member's E-step is dispatched from a
    long-lived worker thread, so one client's host-side block prep
    (padding, mmap reads, prefetch) overlaps another's device compute
    instead of serializing in this loop. Determinism is untouched — the
    per-client payloads are identical jitted computations on identical
    inputs, and the reduction below consumes them in cohort order
    regardless of completion order, so the f32 sum is bit-identical to
    the serial loop (pinned in tests/test_fed_async.py)."""

    kind = "sources"
    host = True

    def __init__(self, sources: Sequence[DataSource], executor=None):
        self.sources = list(sources)
        self.executor = executor

    @property
    def num_clients(self) -> int:
        return len(self.sources)

    @property
    def dim(self) -> int:
        return self.sources[0].dim

    @property
    def sizes(self):
        return [src.num_rows for src in self.sources]

    @property
    def population_clients(self) -> int:
        return self.num_clients

    def reduce_clients(self, local_step, state, cohort=None, weights=None,
                       transform=None, tparams=None, tkey=None):
        """Host-loop the per-client step over the (cohort) streams,
        apply the uplink ``transform`` (if any) per client, and sum."""
        if cohort is None:
            members = range(len(self.sources))
            members_arr = jnp.arange(len(self.sources))
        else:
            # ascending order (samplers sort), so the f32 summation
            # order matches the historical full-population loop
            members = [int(i) for i in np.asarray(cohort)]
            members_arr = jnp.asarray(np.asarray(cohort))
        step = _wrap_step(local_step, state, transform, tparams, tkey,
                          members_arr)
        w = None if weights is None else np.asarray(weights)
        # survivors only: a zero-weight (dropped) client's E-step never
        # runs, on the serial and the concurrent path alike
        jobs = [(pos, i) for pos, i in enumerate(members)
                if w is None or w[pos] != 0.0]
        if self.executor is not None and len(jobs) > 1:
            raw = self.executor.map_ordered(
                lambda i: step(self.sources[i], None, i),
                [i for _, i in jobs])
        else:
            raw = [step(self.sources[i], None, i) for _, i in jobs]
        per = []
        for (pos, i), p in zip(jobs, raw):
            if w is not None and w[pos] != 1.0:
                p = jax.tree.map(
                    lambda s: s * jnp.asarray(w[pos], s.dtype), p)
            per.append(p)
        return jax.tree.map(lambda *s: sum(s), *per)


@jax.tree_util.register_pytree_node_class
class ShardedClients:
    """Mesh-sharded clients: the client axis of ``data (C, N, d)`` maps to
    shards of ``axis``, ``C / shards`` clients each; the per-round combine
    is literally one ``jax.lax.psum`` across the mesh — the collective
    pattern the sharded DEM runtime always had, now produced by the same
    driver as everything else. As on :class:`SplitClients`, each client's
    step reads ``rows`` once :meth:`prepared` has built them."""

    kind = "sharded"
    host = False

    def __init__(self, data: jax.Array, mask: jax.Array, mesh,
                 axis: str = "data", split=None, rows=None):
        self.data = data
        self.mask = mask
        self.mesh = mesh
        self.axis = axis
        self.split = split  # the original ClientSplit (host metadata)
        self.rows = rows

    def tree_flatten(self):
        return (self.data, self.mask, self.rows), (self.mesh, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, mask, rows = children
        return cls(data, mask, *aux, rows=rows)

    def prepared(self, prepare_client) -> "ShardedClients":
        """These clients with ``prepare_client(x, w)`` of every client's
        rows as the rows its step reads, built once: each shard prepares
        its own clients inside ``shard_map``, and the result stays sharded
        like ``data``."""
        spec = P(self.axis)
        rows = jax.shard_map(jax.vmap(prepare_client), mesh=self.mesh,
                             in_specs=(spec, spec), out_specs=spec,
                             check_vma=False)(self.data, self.mask)
        return ShardedClients(self.data, self.mask, self.mesh, self.axis,
                              self.split, rows)

    @property
    def num_clients(self) -> int:
        return self.data.shape[0]

    @property
    def shards(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def clients_per_shard(self) -> int:
        return self.num_clients // self.shards

    @property
    def dim(self) -> int:
        return self.data.shape[-1]

    @property
    def sizes(self):
        return self.split.sizes if self.split is not None else jnp.sum(
            self.mask, axis=1)

    @property
    def population_clients(self) -> int:
        return self.num_clients

    def reduce_clients(self, local_step, state, cohort=None, weights=None,
                       transform=None, tparams=None, tkey=None):
        """Per-shard vmap of the per-client step (with the uplink
        ``transform``, if any, applied per client — its key and traced
        knobs ride the shard_map replicated), then ONE psum."""
        axis = self.axis
        c = self.data.shape[0]
        rows = self.data if self.rows is None else self.rows
        # the transform key/params enter shard_fn as replicated operands
        # (shard_map wants operands explicit, not closed over)
        tk = jnp.zeros((), jnp.int32) if tkey is None else tkey
        tp = () if tparams is None else tparams

        if cohort is None:
            def shard_fn(state, idx_s, w_s, data_s, mask_s, tk_r, tp_r):
                step = _wrap_step(local_step, state, transform, tp_r,
                                  tk_r, jnp.arange(c))
                per = jax.vmap(step)(data_s, mask_s, idx_s)
                if weights is not None:
                    per = jax.tree.map(
                        lambda s: s * _weight_bcast(w_s, s), per)
                local = jax.tree.map(lambda s: jnp.sum(s, axis=0), per)
                # === one all-reduce per round ===
                return jax.tree.map(lambda s: jax.lax.psum(s, axis), local)

            w = jnp.ones((c,)) if weights is None else weights
            fn = jax.shard_map(shard_fn, mesh=self.mesh,
                               in_specs=(P(), P(axis), P(axis), P(axis),
                                         P(axis), P(), P()),
                               out_specs=P(), check_vma=False)
            return fn(state, jnp.arange(c), w, rows, self.mask, tk, tp)

        # Cohort execution: the cohort (and its weights) are replicated;
        # each shard gathers the cohort members IT owns from its local
        # client slab, zero-masks the slots owned elsewhere, and the
        # psum sums the realized contributors. Per-shard compute is
        # O(m), not O(per_shard): membership stays traced, m static.
        m = cohort.shape[0]
        per_shard = c // self.mesh.shape[axis]

        def shard_fn(state, idx_s, cohort_r, w_r, data_s, mask_s, tk_r,
                     tp_r):
            local = cohort_r - idx_s[0]
            owned = (local >= 0) & (local < per_shard)
            safe = jnp.clip(local, 0, per_shard - 1)
            step = _wrap_step(local_step, state, transform, tp_r, tk_r,
                              cohort_r)
            per = jax.vmap(step)(
                jax.tree.map(lambda r: jnp.take(r, safe, axis=0), data_s),
                jnp.take(mask_s, safe, axis=0), cohort_r)
            gate = owned.astype(w_r.dtype) * w_r
            per = jax.tree.map(lambda s: s * _weight_bcast(gate, s), per)
            total = jax.tree.map(lambda s: jnp.sum(s, axis=0), per)
            # === one all-reduce per round ===
            return jax.tree.map(lambda s: jax.lax.psum(s, axis), total)

        w = jnp.ones((m,)) if weights is None else weights
        fn = jax.shard_map(shard_fn, mesh=self.mesh,
                           in_specs=(P(), P(axis), P(), P(), P(axis),
                                     P(axis), P(), P()),
                           out_specs=P(), check_vma=False)
        return fn(state, jnp.arange(c), cohort, w, rows, self.mask, tk, tp)


def slab_counters(backend, lanes_computed: Optional[int] = None,
                  cohort_size: Optional[int] = None,
                  prepared_bytes: Optional[int] = None,
                  row_block: Optional[int] = None) -> dict:
    """Counters of the client data one round computes over, for a
    profiler span: ``clients`` (the cohort's size when one is sampled);
    ``rows``, their real rows; ``rows_computed``, the rows of the padded
    ``(clients, N, d)`` slab the clients' op computes over: every row, or
    where the op skips each client's blocks of ``row_block`` rows past its
    last real row, as the layer that picks its backend gives it, each
    client's rows rounded up to a block; ``lanes``, the feature width d;
    ``lanes_computed``, the width the clients' op computes over, as the
    layer that picks its backend gives it (left out where none does);
    ``prepared_bytes``, the device bytes of padded kernel operands built
    once for the phase, as that layer gives it (left out where the
    kernels get raw arrays; on sharded clients, one chip's). Sharded
    clients add ``shards`` and ``clients_per_shard``; their other counts
    are of the whole federation, like every backend's. Taken from shapes
    and host arrays alone: a count that lives on the device is left out,
    never fetched (that would wait for the device). So are a sampled
    cohort's rows, which change from round to round, and
    ``rows_computed`` for source clients, whose block padding the engine
    owns. A sampled cohort, whose sizes are not known on the host, counts
    every padded row."""
    d = int(backend.dim)
    c = int(backend.num_clients if cohort_size is None else cohort_size)
    out = {"clients": c, "lanes": d}
    if lanes_computed is not None:
        out["lanes_computed"] = int(lanes_computed)
    if prepared_bytes is not None:
        out["prepared_bytes"] = int(prepared_bytes)
    sizes = None
    if cohort_size is None:
        sizes = backend.sizes if backend.kind == "sources" \
            else getattr(getattr(backend, "split", None), "sizes", None)
        if isinstance(sizes, (np.ndarray, list, tuple)):
            out["rows"] = int(np.sum(sizes))
        else:
            sizes = None
    if backend.kind != "sources":
        out["rows_computed"] = c * int(backend.data.shape[1])
        if row_block is not None and sizes is not None:
            blocks = -(-np.asarray(sizes, np.int64) // row_block)
            out["rows_computed"] = int(np.sum(blocks)) * row_block
    if backend.kind == "sharded":
        out.update(shard_counters(backend))
    return out


def shard_counters(backend) -> dict:
    """How sharded clients lie on the mesh, for a profiler span:
    ``shards`` (the mesh axis' size) and ``clients_per_shard``."""
    return {"shards": int(backend.shards),
            "clients_per_shard": int(backend.clients_per_shard)}


def make_backend(clients, mesh=None, axis: str = "data"):
    """THE client dispatch: ClientSplit -> :class:`SplitClients`, a list
    of DataSources -> :class:`SourceClients`; with a ``mesh``, a
    ClientSplit or ``(data, mask)`` arrays -> :class:`ShardedClients`,
    their client axis placed over ``axis`` (no copy where it already
    lies so). The client count must divide by the axis' size."""
    from repro.core.partition import ClientSplit  # call-time: core sits above
    if mesh is not None:
        split = clients if isinstance(clients, ClientSplit) else None
        data, mask = clients[:2] if split is not None else clients
        shards = mesh.shape[axis]
        if data.shape[0] % shards:
            raise ValueError(
                f"{data.shape[0]} clients do not divide over the {shards} "
                f"shards of mesh axis {axis!r}")
        data, mask = jax.device_put((data, mask),
                                    NamedSharding(mesh, P(axis)))
        return ShardedClients(data, mask, mesh, axis, split)
    if isinstance(clients, ClientSplit):
        return SplitClients(jnp.asarray(clients.data),
                            jnp.asarray(clients.mask), clients)
    if (isinstance(clients, (list, tuple)) and len(clients) > 0
            and all(isinstance(s, DataSource) for s in clients)):
        return SourceClients(clients)
    raise TypeError(
        f"federated clients must be a ClientSplit, a non-empty list of "
        f"DataSources, or (data, mask) arrays with a mesh; got "
        f"{type(clients).__name__}")


# ----------------------------------------------------------------------
# The round driver
# ----------------------------------------------------------------------

def _round(strategy, state, backend, cohort=None, weights=None,
           transform=None, tparams=None, rkey=None):
    """One full round: client updates -> (transformed) uplink -> reduce
    -> transform ``finish`` -> server combine. ``cohort``/``weights``
    come from the driver's sampler and straggler policy (None = full
    participation, everyone on time); ``transform``/``tparams``/``rkey``
    from the driver's uplink-transform seam (§11; ``rkey`` is already
    folded per round). The named scopes mark the round's phases in the
    compiled program's op metadata, for a profiler's op view: inside
    ``reduce``, ``client_step`` is one client's update and the rest is
    the sum over clients."""
    def client_step(state, x, w, idx):
        with jax.named_scope("client_step"):
            return strategy.local_step(state, x, w, idx)

    with jax.named_scope("reduce"):
        total = backend.reduce_clients(client_step, state, cohort,
                                       weights, transform=transform,
                                       tparams=tparams, tkey=rkey)
        if transform is not None:
            total = transform.finish(total)
    with jax.named_scope("combine"):
        return strategy.server_combine(state, total)


def _keep_going(strategy, state):
    """Loop-continuation predicate: the strategy's own ``keep_going``
    when it has one (EM-style ``delta > tol``, which also halts on a NaN
    scalar exactly like the pre-§9 loops), else ``not converged``."""
    kg = getattr(strategy, "keep_going", None)
    if kg is not None:
        return kg(state)
    return jnp.logical_not(strategy.converged(state))


def _cohort_and_weights(sampler, stragglers, backend, skey, dkey, rnd):
    """Resolve round ``rnd``'s cohort indices and straggler weights from
    the driver-owned policies. Keys are traced, policies static: which
    clients participate can change every round (and every reseed)
    without adding a jit cache entry."""
    cohort = None if sampler is None else sampler.cohort(skey, rnd)
    weights = None
    if stragglers is not None:
        members = cohort if cohort is not None \
            else jnp.arange(backend.num_clients)
        weights = stragglers.drop_mask(dkey, rnd, members)
    return cohort, weights


@partial(jax.jit, static_argnames=("strategy", "max_rounds", "sampler",
                                   "stragglers", "transform"))
def _iterate_jit(strategy, backend, state0, max_rounds: int,
                 sampler=None, stragglers=None, transform=None,
                 skey=None, dkey=None, tkey=None, tparams=None):
    """Resident-client round loop as ONE jitted ``lax.while_loop`` —
    bootstrap round, then iterate while ``keep_going``. Structurally the
    pre-§9 ``_dem_loop``: same state transitions, same cond arithmetic,
    so re-landed strategies reproduce their history bit for bit. The
    strategy, sampler, straggler policy and uplink transform are static
    arguments (hashable frozen dataclasses); numeric knobs that sweep
    (tol, reg_covar, the transform's epsilon/delta) ride in ``state0`` /
    ``tparams`` as traced leaves and the sampler/straggler/transform
    PRNG keys (``skey``/``dkey``/``tkey``) are traced, so sweeping knobs
    or reseeding does not recompile. A strategy's ``prepare_client``
    runs on the clients once, before the loop (on sharded clients each
    shard its own), whose body closes over the result (never a carry
    element)."""
    prepare = getattr(strategy, "prepare_client", None)
    if prepare is not None:
        backend = backend.prepared(prepare)

    def one_round(state, rnd):
        cohort, weights = _cohort_and_weights(sampler, stragglers, backend,
                                              skey, dkey, rnd)
        rkey = None if transform is None else jax.random.fold_in(tkey, rnd)
        return _round(strategy, state, backend, cohort, weights,
                      transform, tparams, rkey)

    def cond(carry):
        state, it = carry
        return jnp.logical_and(it < max_rounds, _keep_going(strategy, state))

    def body(carry):
        state, it = carry
        return one_round(state, it), it + 1

    state1 = one_round(state0, jnp.array(0))
    state, it = jax.lax.while_loop(cond, body, (state1, jnp.array(1)))
    return state, it


class _CohortView:
    """Accounting proxy the driver hands to ``round_payload`` when a
    sampler is in play: ``num_clients`` is the cohort size m (what a
    round actually moves), ``population_clients`` stays the population C
    (what once-per-run init traffic touches). Strategies keep writing
    per-round arithmetic against ``backend.num_clients`` and it stays
    correct under sampling."""

    def __init__(self, backend, cohort_size: int):
        self._backend = backend
        self.num_clients = int(cohort_size)
        self.population_clients = backend.num_clients
        self.kind = backend.kind
        self.host = backend.host

    @property
    def dim(self) -> int:
        return self._backend.dim


_TRANSFORM_METHODS = ("apply", "finish", "traced", "wire_itemsize",
                      "epsilon_per_round")


def _validate_transform(transform):
    """Duck-type + hashability check of a transform before it becomes a
    static jit argument (an unhashable transform would raise deep inside
    jit with a far worse message)."""
    missing = [m for m in _TRANSFORM_METHODS
               if not callable(getattr(transform, m, None))]
    if missing:
        raise TypeError(
            f"transform {type(transform).__name__} is missing "
            f"{missing}; see repro.fed.transforms.PayloadTransform")
    try:
        hash(transform)
    except TypeError as e:
        raise TypeError(
            f"transform {type(transform).__name__} must be hashable "
            f"(frozen dataclass) to ride the jitted round loop as a "
            f"static argument") from e


def run_rounds(strategy, clients, *, key: Optional[jax.Array] = None,
               state0=None, max_rounds: int = 1, mesh=None,
               axis: str = "data", sampler=None, stragglers=None,
               transform=None, executor=None):
    """Run a :class:`FederationStrategy` to convergence — THE round loop.

    Owns everything that used to be copy-pasted per algorithm: the client
    input dispatch (:func:`make_backend`), the round loop (jitted
    while_loop for resident/sharded clients, host loop for sources), the
    bootstrap round, the round budget, cohort sampling, straggler drops,
    and the communication ledger (realized rounds x the strategy's
    :class:`RoundPayload`).

    ``state0`` overrides the strategy's own ``init_state`` (the sharded
    DEM entry point passes externally chosen init centers this way);
    otherwise ``key`` seeds it.

    ``sampler`` (``repro.fed.cohort``: :class:`CyclicSampler` /
    :class:`UniformSampler`) makes each round compute ONLY its sampled
    cohort — cost scales with m, not the population — and resizes the
    per-round ledger to the cohort. ``stragglers``
    (:class:`ArrivalStragglers`) drops the round's slowest arrivals to
    exact-zero contribution. Both are driver-owned and strategy-agnostic:
    any iterative strategy runs under them unchanged (one-shot strategies
    reject them — there is no round structure to sample).

    ``transform`` (a ``repro.fed.transforms`` :class:`PayloadTransform`,
    §11) is applied to every client's uplink between ``local_step`` and
    the backend reduce — DP noise, stochastic quantization, secure-agg
    masking, or a :class:`~repro.fed.transforms.Compose` of them. The
    transform is a static argument; its seed and swept knobs (epsilon,
    delta) enter as traced leaves, so re-seeding or re-budgeting never
    recompiles. The ledger picks up the transform's uplink dtype and
    cumulative ``epsilon_spent``.

    ``executor`` (a :class:`repro.fed.async_runtime.ClientExecutor`)
    applies to the source-client backend only: the host round loop fans
    each cohort's per-client steps out to the executor's long-lived
    workers and reduces in deterministic cohort order — same bits,
    overlapped wall-clock. Resident/sharded backends (already one fused
    program) ignore it."""
    backend = make_backend(clients, mesh, axis)
    if executor is not None and backend.host:
        backend.executor = executor
    one_shot = getattr(strategy, "one_shot", False)
    skey = dkey = tkey = tparams = None
    if transform is not None:
        _validate_transform(transform)
        if one_shot and getattr(transform, "additive_only", False):
            raise ValueError(
                f"{type(transform).__name__} masks only cancel in an "
                f"additive aggregate; a one-shot strategy's server reads "
                f"each client payload individually, so the combination "
                f"is meaningless")
        tkey = jax.random.key(int(getattr(transform, "seed", 0)))
        tparams = transform.traced()
    if sampler is not None:
        if one_shot:
            raise ValueError(
                "cohort sampling needs a round structure; one-shot "
                "strategies take no sampler")
        if sampler.num_clients != backend.num_clients:
            raise ValueError(
                f"sampler is sized for {sampler.num_clients} clients but "
                f"the backend has {backend.num_clients}")
        skey = jax.random.key(int(getattr(sampler, "seed", 0)))
    if stragglers is not None:
        if one_shot:
            raise ValueError(
                "straggler handling needs a round structure; one-shot "
                "strategies take no straggler policy")
        dkey = jax.random.key(int(getattr(stragglers, "seed", 0)))
    # the phases' bytes of padded kernel operands built once, and of the
    # init's all_gather, as the strategy, which picks the clients' backend
    # and the init, gives them
    prepared = getattr(strategy, "prepared_bytes", None)
    gathered = getattr(strategy, "gathered_bytes", None)

    def prepared_in(phase):
        return None if prepared is None else prepared(backend, phase)

    ledger_backend = backend if sampler is None \
        else _CohortView(backend, sampler.cohort_size)
    if state0 is None:
        init = {"prepared_bytes": prepared_in("init")}
        if backend.kind == "sharded":
            init.update(shard_counters(backend), allgather_bytes=(
                None if gathered is None else gathered(backend)))
        with TraceAnnotation("repro.rounds.init", **{
                name: v for name, v in init.items() if v is not None}):
            state0 = strategy.init_state(key, backend)

    cohort_size = None if sampler is None else sampler.cohort_size
    # the loop spans' counters; the strategy gives the width the clients
    # compute over
    lanes = getattr(strategy, "lanes_computed", None)
    block = getattr(strategy, "row_block", None)
    slab = None if one_shot else slab_counters(
        backend, None if lanes is None else lanes(int(backend.dim)),
        cohort_size, prepared_in("loop"), None if block is None else block())
    if backend.kind == "sharded" and not one_shot:
        # one round's psum carries one client's payload, summed
        per_round = strategy.round_payload(ledger_backend, state0)
        slab["allreduce_bytes"] = (per_round.uplink_floats
                                   // ledger_backend.num_clients
                                   * per_round.itemsize)
    if one_shot:
        if transform is not None:
            state = strategy.run_once(state0, backend,
                                      transform=transform,
                                      tparams=tparams, tkey=tkey)
        else:
            state = strategy.run_once(state0, backend)
        rounds, n_rounds, converged = 1, jnp.asarray(1), True
    elif backend.host:
        def host_round(state, rnd):
            with TraceAnnotation("repro.rounds.round"):
                cohort, weights = _cohort_and_weights(
                    sampler, stragglers, backend, skey, dkey, rnd)
                if cohort is not None:
                    cohort = np.asarray(cohort)
                rkey = None if transform is None \
                    else jax.random.fold_in(tkey, rnd)
                return _round(strategy, state, backend, cohort, weights,
                              transform, tparams, rkey)

        with TraceAnnotation("repro.rounds.loop", **slab):
            state = host_round(state0, 0)
            it = 1
            while it < max_rounds and bool(_keep_going(strategy, state)):
                state = host_round(state, it)
                it += 1
            rounds, n_rounds = it, jnp.asarray(it)
            converged = bool(strategy.converged(state))
    else:
        with TraceAnnotation("repro.rounds.loop", **slab):
            state, n_rounds = _iterate_jit(strategy, backend, state0,
                                           max_rounds, sampler, stragglers,
                                           transform, skey, dkey, tkey,
                                           tparams)
            rounds = int(n_rounds)
            converged = bool(strategy.converged(state))

    with TraceAnnotation("repro.rounds.finalize", rounds=rounds):
        # Optional once-per-run epilogue (e.g. FedKMeans rescoring its
        # final centers); runs eagerly after the loop, before the ledger
        # is drawn up so the strategy's RoundPayload can account for it.
        post = getattr(strategy, "post_rounds", None)
        if post is not None and not one_shot:
            state = post(state, backend)

        payload = strategy.round_payload(ledger_backend, state)
        if transform is not None:
            # transform-aware ledger: the uplink direction carries the
            # wire dtype the transform produced, and the accountant's
            # per-round spend scales by the realized rounds into
            # epsilon_spent
            payload = payload._replace(
                uplink_itemsize=transform.wire_itemsize(payload.itemsize),
                epsilon_per_round=float(transform.epsilon_per_round()))
        comm = payload.totals(rounds)
        return strategy.finalize(state, n_rounds, converged, comm)
