"""The continuous-batching GMM scoring engine (DESIGN.md §10).

One queue, one fixed :class:`~repro.serve.slots.SlotPool`, one jitted
scoring step::

    submit -> [queue] -> admit into free slots -> jitted score step
                 ^            (mid-flight)        (ONE compiled shape,
                 |                                 donated slab buffers)
                 +---- retire finished requests <--+

Each :meth:`ScoringEngine.step` call is one micro-batch: poll the
attached model store, finish a pending hot swap if the pool has drained,
admit queued requests into free slots, score the ``(slots,
rows_per_slot, d)`` slab in one jitted call (slab and mask buffers are
donated — XLA reuses their memory for the outputs), and harvest/retire.
Each of those phases is a ``repro.serve.*`` profiler span (DESIGN.md
§13).
Requests longer than ``rows_per_slot`` stream through their slot across
micro-batches; short ones are padded to the static shape, so the hot
path compiles exactly once per ``(slots, rows_per_slot, d, K, mode,
backend)`` — admitting, retiring and re-seeding requests never retraces.

**Hot model swap** (the drain-and-install protocol): :meth:`install` (or
a newer version appearing in the attached store) marks the new model
*pending* — admission stops, in-flight requests keep scoring under the
old model, and the instant the pool drains the new model is installed
and admission resumes. The guarantee: every request is scored by exactly
ONE model version — the one echoed in its result — so per-request scores
are bit-identical to a single-model engine that only ever held that
version, no request is ever dropped, and the version tag observed across
the retirement stream flips at exactly one admission boundary. The cost
is a bounded admission pause (the tail of the longest in-flight
request), measured per swap in :attr:`ScoringEngine.swap_pauses` and
tracked as the ``swap`` section of ``BENCH_serve.json``.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from functools import partial
from typing import List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.config import resolve_backend
from repro.core.em import _log_prob_block
from repro.core.gmm import GMM
from repro.serve.slots import InFlight, SlotPool
from repro.serve.types import ScoreConfig, ScoreRequest, ScoreResult


@partial(jax.jit, static_argnames=("mode", "backend"),
         donate_argnums=(1, 2))
def _score_slab(gmm: GMM, slab: jax.Array, mask: jax.Array, *,
                mode: str, backend: str):
    """THE jitted scoring step: ``(S, R, d)`` slab + ``(S, R)`` row mask
    -> ``(S, R)`` scores (log_prob/anomaly) or ``(S, R, K)``
    responsibilities.

    Per-row math is exactly the training engine's
    (``repro.core.em._log_prob_block`` — kernel-dispatched, so "fused"
    rides the Pallas ``gmm_logpdf`` on TPU), so engine scores agree with
    ``repro.api.log_prob`` to a few float32 ulps: the same math, but XLA
    may accumulate a dot product in another order at another batch
    shape. Masked padding rows are multiplied to zero AFTER the per-row
    computation (``x * 1.0`` is exact in IEEE f32, so valid rows are
    untouched). ``slab`` and ``mask`` are donated — both are dead after
    the call (the engine rebuilds them host-side every micro-batch), and
    XLA aliases whatever shapes line up (the ``(S, R)`` mask buffer
    becomes the ``(S, R)`` score buffer in log_prob/anomaly mode; the
    rest is simply freed early). The engine suppresses XLA's "donated
    buffer not usable" note for the shapes that can't alias."""
    s, r, d = slab.shape
    x = slab.reshape(s * r, d)
    if mode == "responsibilities":
        if backend == "fused":
            from repro.kernels import ops  # kernels are optional
            lp = ops.gmm_logpdf(x, gmm.means, gmm.covs,
                                jnp.log(gmm.weights))
            resp = jax.nn.softmax(lp, axis=1)
        else:
            resp = gmm.responsibilities(x)
        k = resp.shape[-1]
        return resp.reshape(s, r, k) * mask[:, :, None]
    lp = _log_prob_block(gmm, x, backend).reshape(s, r) * mask
    return lp if mode == "log_prob" else -lp


class ScoringEngine:
    """Serve one global GMM to a stream of scoring requests.

    - ``gmm``: the model to serve (diag or full covariance; shapes
      ``weights (K,)``, ``means (K, d)``, ``covs (K, d)|(K, d, d)``).
    - ``config``: a :class:`~repro.serve.types.ScoreConfig` (mode, slot
      pool geometry, backend, store poll cadence).
    - ``version``: tag echoed in every result scored by this model.
    - ``store``: optional subscription — any object with a ``poll()``
      returning an object with ``.version``/``.gmm`` attributes for a
      newly published model, or None (``repro.serve.ModelStore`` is the
      canonical one). Polled every ``config.poll_every`` micro-batches;
      a new version triggers the drain-and-install swap.

    Streaming use is ``submit`` + repeated ``step``; offline convenience
    is ``run(requests)`` (submit all, drain, return every result).
    Results surface in retirement order; ``rid`` maps them back.
    """

    def __init__(self, gmm: GMM, config: Optional[ScoreConfig] = None, *,
                 version: Union[int, str] = "v0", store=None):
        self.config = config if config is not None else ScoreConfig()
        if not isinstance(self.config, ScoreConfig):
            raise TypeError(f"config must be a ScoreConfig, "
                            f"got {type(self.config).__name__}")
        self._store = store
        # (request, time.perf_counter() at submit), FIFO
        self._queue: deque = deque()
        self._pending: Optional[tuple] = None     # (gmm, version)
        self._pending_since: Optional[float] = None
        self.steps = 0
        self.swaps = 0
        self.completed = 0
        #: seconds each completed swap stalled admission (drain time)
        self.swap_pauses: List[float] = []
        self._pool = SlotPool(self.config.slots, self.config.rows_per_slot,
                              int(gmm.n_features))
        self._set_model(gmm, version)

    # -- model ----------------------------------------------------------

    @property
    def version(self) -> Union[int, str]:
        """Version tag of the currently installed model (new admissions
        are scored — and tagged — with this)."""
        return self._version

    @property
    def gmm(self) -> GMM:
        """The currently installed model (a device-resident GMM)."""
        return self._gmm

    @property
    def dim(self) -> int:
        """Feature dimension every request's rows must match."""
        return self._pool.dim

    @property
    def backend(self) -> str:
        """The scoring implementation the config's backend resolved to
        for the installed model: ``"fused"`` (Pallas ``gmm_logpdf``) or
        ``"reference"`` (pure jnp)."""
        return self._backend

    @property
    def swap_pending(self) -> bool:
        """True while a newer model waits for in-flight requests to
        drain (admission is stalled)."""
        return self._pending is not None

    def _set_model(self, gmm: GMM, version: Union[int, str]) -> None:
        if not isinstance(gmm, GMM):
            raise TypeError(f"engine serves a repro.core.gmm.GMM, "
                            f"got {type(gmm).__name__}")
        if int(gmm.n_features) != self._pool.dim:
            raise ValueError(
                f"model dim {int(gmm.n_features)} != engine dim "
                f"{self._pool.dim}; a swap cannot change the feature "
                f"dimension")
        self._gmm = jax.device_put(gmm)
        self._version = version
        # "auto" resolves per model: the fused kernel serves diag
        # covariances only (same rule as training).
        self._backend = resolve_backend(self.config.backend,
                                        fused_supported=gmm.is_diagonal)

    def install(self, gmm: GMM, version: Union[int, str]) -> None:
        """Hot-swap to a new model. Installs immediately when no request
        is in flight; otherwise the swap goes *pending*: admission stops,
        in-flight requests finish under the old model, and the install
        lands the moment the pool drains (within the step that retires
        the last of them). A second install while pending replaces the
        pending model (latest wins) but keeps the original stall clock."""
        if self._pool.idle:
            self._set_model(gmm, version)
            self.swaps += 1
            return
        if self._pending_since is None:
            self._pending_since = time.perf_counter()
        self._pending = (gmm, version)

    def _finish_swap_if_drained(self) -> None:
        if self._pending is not None and self._pool.idle:
            gmm, version = self._pending
            self._pending = None
            if self._pending_since is not None:
                self.swap_pauses.append(time.perf_counter()
                                        - self._pending_since)
                self._pending_since = None
            self._set_model(gmm, version)
            self.swaps += 1

    def _poll_store(self) -> None:
        if self._store is None or self.steps % self.config.poll_every:
            return
        published = self._store.poll()
        if published is not None:
            self.install(published.gmm, published.version)

    @classmethod
    def from_store(cls, store, config: Optional[ScoreConfig] = None,
                   *, follow: bool = True) -> "ScoringEngine":
        """Build an engine serving the latest model published in
        ``store`` (a :class:`repro.serve.ModelStore`). ``follow=True``
        keeps the subscription attached, so later publishes hot-swap in;
        ``follow=False`` pins the latest version forever. Raises
        :class:`FileNotFoundError` when nothing has been published."""
        published = store.latest()
        if published is None:
            raise FileNotFoundError(
                f"model store {store.root!r} has no published model yet")
        return cls(published.gmm, config, version=published.version,
                   store=store if follow else None)

    # -- the request stream --------------------------------------------

    @property
    def queued(self) -> int:
        """Requests submitted but not yet admitted to a slot."""
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        """Requests currently occupying slots (admitted, not retired)."""
        return self._pool.in_flight

    @property
    def pending_requests(self) -> int:
        """Requests the engine still owes results for (queued plus in
        flight) — ``drain`` loops until this reaches zero."""
        return self.queued + self.in_flight

    def submit(self, request: ScoreRequest) -> None:
        """Enqueue one request (FIFO). Validates the feature dimension
        against the served model now, so a malformed request fails at the
        submit site, not mid-micro-batch."""
        if not isinstance(request, ScoreRequest):
            raise TypeError(f"submit takes a ScoreRequest, "
                            f"got {type(request).__name__}")
        if request.rows.shape[1] != self.dim:
            raise ValueError(
                f"request {request.rid}: rows have dim "
                f"{request.rows.shape[1]}, the served model expects "
                f"{self.dim}")
        self._queue.append((request, time.perf_counter()))

    def _admit(self, results: List[ScoreResult]) -> tuple[int, float]:
        """Fill free slots from the queue (FIFO) -> (requests admitted,
        their summed seconds in the queue). Blocked entirely while a swap
        is pending — that is the drain half of the protocol. Zero-row
        requests retire immediately (they still consume an admission, so
        their version tag honors the swap boundary)."""
        admitted, waited = 0, 0.0
        if self._pending is not None:
            return admitted, waited
        now = time.perf_counter()
        while self._queue:
            head, submitted = self._queue[0]
            if head.num_rows == 0:
                trailing = ((int(self._gmm.n_components),)
                            if self.config.mode == "responsibilities"
                            else ())
                results.append(self._pool.retire_empty(
                    InFlight(head, submitted, self._version), trailing))
                self.completed += 1
            elif self._pool.free == 0:
                break
            else:
                self._pool.admit(InFlight(head, submitted, self._version))
            self._queue.popleft()
            admitted += 1
            waited += now - submitted
        return admitted, waited
    # -- micro-batches --------------------------------------------------

    def step(self) -> List[ScoreResult]:
        """Run ONE micro-batch -> the requests that finished in it.

        Order of operations: poll the store -> finish a drained swap ->
        admit into free slots -> one jitted scoring call over the slab ->
        harvest/retire -> finish the swap again if those retirements
        drained the pool (so the stall never lasts longer than the drain
        itself). A fully idle step returns ``[]``."""
        with TraceAnnotation("repro.serve.step"):
            self.steps += 1
            results: List[ScoreResult] = []
            with TraceAnnotation("repro.serve.admit"):
                self._poll_store()
                self._finish_swap_if_drained()
                admitted, waited = self._admit(results)
            pool = self._pool
            with TraceAnnotation("repro.serve.stage", admitted=admitted,
                                 queue_wait_us=int(waited * 1e6),
                                 queued=len(self._queue)):
                active = pool.stage()
            if active:
                with TraceAnnotation(
                        "repro.serve.put", rows=pool.staged_rows,
                        rows_computed=pool.slots * pool.rows_per_slot,
                        h2d_bytes=pool.slab.nbytes + pool.mask.nbytes):
                    slab = jnp.asarray(pool.slab)
                    mask = jnp.asarray(pool.mask)
                with TraceAnnotation("repro.serve.score"), \
                        warnings.catch_warnings():
                    # Donation is deliberate (both buffers are rebuilt
                    # every micro-batch); XLA notes the shapes it cannot
                    # alias.
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    out = _score_slab(self._gmm, slab, mask,
                                      mode=self.config.mode,
                                      backend=self._backend)
                with TraceAnnotation("repro.serve.fetch"):
                    out = np.asarray(out)
                with TraceAnnotation("repro.serve.harvest"):
                    finished = pool.harvest(out, active)
                self.completed += len(finished)
                results.extend(finished)
            self._finish_swap_if_drained()
            return results

    def drain(self) -> List[ScoreResult]:
        """Step until every submitted request has retired -> all results
        (retirement order). A pending swap cannot stall this: once the
        pool drains it installs and admission resumes."""
        results: List[ScoreResult] = []
        while self.pending_requests:
            results.extend(self.step())
        return results

    def run(self, requests) -> List[ScoreResult]:
        """Offline convenience: submit every request, drain, return all
        results (retirement order; match them back by ``rid``)."""
        for request in requests:
            self.submit(request)
        return self.drain()
