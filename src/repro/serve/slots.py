"""The fixed slot pool behind continuous batching (DESIGN.md §10).

The pool owns the ONE static device-facing shape of the hot path: a
``(slots, rows_per_slot, d)`` f32 slab plus its ``(slots, rows_per_slot)``
0/1 row mask. Requests are admitted into free slots *mid-flight* — there
are no lockstep waves — and a request longer than ``rows_per_slot``
streams through its slot across micro-batches, its cursor advancing
``rows_per_slot`` rows per step. Short requests are zero-padded to the
static shape, so the jitted scoring step compiles exactly once per
``(slots, rows_per_slot, d, K, mode, backend)`` and admission, progress
and retirement are pure host bookkeeping.

Nothing here touches jax: the pool stages NumPy buffers (which the engine
transfers and donates to the scoring step) and accumulates per-request
output chunks. The engine owns the model, the jitted step, and the swap
protocol.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Union

import numpy as np

from repro.serve.types import ScoreRequest, ScoreResult


@dataclasses.dataclass
class InFlight:
    """Host bookkeeping of one admitted request: the cursor into its rows
    and the output chunks harvested so far. ``version`` is pinned at
    admission — the swap protocol guarantees it is the version of every
    model that touches this request. ``submitted_s`` is the request's
    ``time.perf_counter()`` at submit."""

    request: ScoreRequest
    submitted_s: float
    version: Union[int, str]
    cursor: int = 0
    chunks: List[np.ndarray] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        """True once every row of the request has been scored."""
        return self.cursor >= self.request.num_rows


class SlotPool:
    """Fixed pool of ``slots`` request slots over one static slab shape.

    The engine's per-micro-batch protocol is three calls:

    1. :meth:`admit` queued requests into free slots (any time, including
       while other slots are mid-request — that is the "continuous" in
       continuous batching);
    2. :meth:`stage` — write each active slot's next
       ``<= rows_per_slot``-row window into the slab/mask buffers;
    3. :meth:`harvest` the step's ``(slots, rows_per_slot[, K])`` output
       back into per-request chunks, retiring finished requests.
    """

    def __init__(self, slots: int, rows_per_slot: int, dim: int):
        if slots < 1 or rows_per_slot < 1 or dim < 1:
            raise ValueError(
                f"slots, rows_per_slot and dim must be positive, got "
                f"({slots}, {rows_per_slot}, {dim})")
        self.slots = slots
        self.rows_per_slot = rows_per_slot
        self.dim = dim
        self.slab = np.zeros((slots, rows_per_slot, dim), np.float32)
        self.mask = np.zeros((slots, rows_per_slot), np.float32)
        #: real (unpadded) rows the last :meth:`stage` wrote
        self.staged_rows = 0
        self._entries: List[Optional[InFlight]] = [None] * slots

    # -- occupancy ------------------------------------------------------

    @property
    def in_flight(self) -> int:
        """Number of occupied slots (requests admitted, not yet retired)."""
        return sum(e is not None for e in self._entries)

    @property
    def free(self) -> int:
        """Number of slots currently available for admission."""
        return self.slots - self.in_flight

    @property
    def idle(self) -> bool:
        """True when no request is in flight."""
        return self.in_flight == 0

    # -- the three-call protocol ---------------------------------------

    def admit(self, entry: InFlight) -> int:
        """Bind an in-flight entry to the first free slot -> slot index.
        Raises :class:`RuntimeError` when the pool is full (the engine
        checks ``free`` first; the queue absorbs overflow)."""
        for s, occupant in enumerate(self._entries):
            if occupant is None:
                self._entries[s] = entry
                return s
        raise RuntimeError("slot pool is full; check .free before admit")

    def stage(self) -> List[int]:
        """Write each active slot's next row window into the slab and
        mask buffers (zero-padding the tail) -> the list of active slot
        indices this micro-batch. Inactive slots get mask 0; their stale
        slab rows are dead weight the mask cancels."""
        active = []
        self.staged_rows = 0
        for s, entry in enumerate(self._entries):
            if entry is None:
                self.mask[s] = 0.0
                continue
            rows = entry.request.rows[
                entry.cursor: entry.cursor + self.rows_per_slot]
            take = rows.shape[0]
            self.slab[s, :take] = rows
            self.slab[s, take:] = 0.0
            self.mask[s, :take] = 1.0
            self.mask[s, take:] = 0.0
            self.staged_rows += take
            active.append(s)
        return active

    def harvest(self, out: np.ndarray,
                active: List[int]) -> List[ScoreResult]:
        """Slice the step output ``out`` (``(slots, rows_per_slot[, K])``)
        back into the active requests' chunk lists, advance their
        cursors, and retire every request whose rows are exhausted ->
        the finished :class:`ScoreResult` list (slots are freed)."""
        results: List[ScoreResult] = []
        now = time.perf_counter()
        for s in active:
            entry = self._entries[s]
            take = min(entry.request.num_rows - entry.cursor,
                       self.rows_per_slot)
            entry.chunks.append(np.asarray(out[s, :take]))
            entry.cursor += take
            if entry.done:
                scores = (np.concatenate(entry.chunks, axis=0)
                          if entry.chunks else
                          np.zeros((0,) + out.shape[2:], np.float32))
                results.append(ScoreResult(
                    rid=entry.request.rid, scores=scores,
                    model_version=entry.version,
                    latency_s=now - entry.submitted_s))
                self._entries[s] = None
        return results

    def retire_empty(self, entry: InFlight,
                     trailing: tuple = ()) -> ScoreResult:
        """Zero-row requests never occupy a slot: retire one directly
        with an empty, correctly-shaped score array (``trailing`` is
        ``(K,)`` in responsibilities mode, ``()`` otherwise)."""
        return ScoreResult(
            rid=entry.request.rid,
            scores=np.zeros((0,) + tuple(trailing), np.float32),
            model_version=entry.version,
            latency_s=time.perf_counter() - entry.submitted_s)
