"""Continuous pub-sub serve loop: bounded ingest, adaptive batching,
K-deep in-flight dispatch, latency SLOs.

This is the piece that turns the repo's batch drivers into a *service*:
the paper's whole pitch is filtering under "very high input ratios"
where per-document processing *time* — not just steady-state
throughput — is what matters, and a fixed-request-list driver cannot
measure that.  The loop is the software analogue of the
admission-controlled reconfigurable stream processor in Diba (see
PAPERS.md): documents arrive continuously, are admitted against a
bounded queue, batched adaptively, filtered on device, and delivered to
subscribers in order — with every stage's occupancy observable.

Dataflow (one :class:`ServeLoop` instance)::

      submit()                  batcher                workers (≤ K)
    ───────────►  ingest queue ─────────►  adaptive  ─────────────►
     admission    (≤ queue_cap)            batching    bytes→verdict
     shed|block                         size OR deadline
                                                            │ FIFO
      deliver()  ◄───────────  completer  ◄─────────────────┘
     subscribers    ordered     fan-out + latency timestamps

* **Admission control** — the ingest queue is bounded at ``queue_cap``;
  an arrival that finds it full is *shed* (counted, its ticket marked)
  or *blocks* the producer (``overload="block"``) until the loop
  drains.  Overload can never grow memory without bound.
* **Adaptive batching** — a batch closes on *size* (``max_batch``
  requests) or *deadline* (``deadline_ms`` after it opened), whichever
  fires first: full batches under load, bounded waiting when idle.
* **K-deep pipelining** — up to ``max_inflight`` closed batches may be
  in flight at once (the generalization of the 2-deep double buffer in
  :meth:`~repro.data.filter_stage.FilterStage.route_bytes_pipelined`);
  the batcher blocks when all K slots are busy, which is the explicit
  *backpressure* signal (counted in ``backpressure_waits``).
* **Ordered delivery** — a single completer thread resolves batches in
  dispatch order, so every subscriber sees its documents in admission
  order regardless of K and regardless of which worker finished first.
  Verdicts are bit-identical to the synchronous
  :meth:`~repro.data.filter_stage.FilterStage.route_bytes` path —
  batching and pipelining are schedule, not semantics.
* **SLOs** — every request is timestamped at admission, when its batch
  closes, at dispatch, when its match list is on the host and after its
  delivery; :meth:`ServeLoop.slo_summary` reports p50/p99/p999
  admission→delivery latency, shed rate, batch fill, close-reason
  counts, queue depth and backpressure occupancy.
* **Spans** — the batcher's waits, the worker's packing, launch, device
  wait and expansion, and the completer's fan-out and delivery are
  :mod:`repro.core.spans`: each adds its seconds to a counter
  (``slo_summary()`` for the loop's, ``FilterStage.stats`` for the
  worker's) and, under a running ``jax.profiler``, writes an
  ``xf.<name>`` event with ``batch=<dispatch sequence number>`` into the
  trace beside the device's ops.  Compiles while the loop is open are
  counted too (``compiles``, ``compile_s``).

Fault tolerance (the loop keeps serving through all of these):

* **Pre-admission validation** — :func:`repro.core.events.validate_payload`
  rejects known-bad bytes at :meth:`ServeLoop.submit` with a typed
  :class:`~repro.core.events.DocumentError` before they ever reach a
  kernel (``rejected`` counter; the ticket carries the error).
* **Poison isolation** — a batch whose device call raises is retried
  once (transient faults), then bisected to isolate the poison
  document(s); a typed error carrying ``doc_indices`` short-circuits
  the bisection.  Poison requests are *quarantined* into a bounded
  dead-letter buffer (:attr:`ServeLoop.dead_letter`) with their typed
  error; the co-batched healthy requests are re-filtered and complete
  with verdicts bit-identical to a fault-free run.  An error that every
  subset reproduces is the device path's, not a document's: it fails
  the loop (:meth:`ServeLoop.close` re-raises it) instead of
  quarantining every request.
* **Shadow-plan hot swap** — :meth:`ServeLoop.subscribe` /
  :meth:`unsubscribe` / :meth:`rebalance` build the replacement plan on
  a background builder thread (``FilterStage.prepare_*``) and the
  completer commits it atomically at a batch boundary — churn never
  drains the queue and never stalls the latency path.  A failed build
  rolls back (``swap_rollbacks``): the live plan is untouched.
  In-flight batches are pinned to the :class:`~repro.data.filter_stage.PlanEpoch`
  they were dispatched under, so a swap can never tear a batch.

Arrival-trace helpers (:func:`poisson_arrivals`, :func:`burst_arrivals`,
:func:`replay_arrivals`) generate the seeded workloads the latency
benchmarks and the CI serve job drive through :func:`run_trace`.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax.monitoring
import numpy as np

from ..core import spans
from ..core.engines import FilterResult
from ..core.events import (DEFAULT_MAX_DEPTH, DocumentError, KernelFault,
                           validate_payload)
from ..data.filter_stage import FilterStage, PlanEpoch, RoutedDocument

#: admission policies: drop the arrival (count it) vs stall the producer
OVERLOAD_POLICIES = ("shed", "block")
#: JAX's event for one backend compile (seconds)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class ServeRequest:
    """One submitted payload's ticket through the loop.

    ``seq`` is the admission sequence number — it doubles as the
    document index in every :class:`RoutedDocument` the request fans out
    to, so delivery order per subscriber is admission order.  Shed
    requests never get a ``seq`` (they were never admitted); neither do
    requests rejected by pre-admission validation.

    ``error`` is the terminal failure state: a typed
    :class:`~repro.core.events.DocumentError` for rejected/quarantined
    poison documents, or the raw worker exception when the loop runs
    with ``recover=False``.  Exactly one of ``routed`` / ``error`` /
    ``shed`` describes a finished ticket.

    Stamps on the loop's clock: ``t_submit`` at admission, ``t_close``
    when its batch closed, ``t_dispatch`` when the batch went to a
    worker, ``t_verdict`` when the worker had its match list on the
    host, ``t_delivered`` after the ``deliver`` callback returned.
    ``done`` is set before the delivery, so ``t_delivered`` may still be
    ``None`` just after ``done`` fires.
    """

    payload: bytes
    t_submit: float
    seq: int = -1
    shed: bool = False
    t_close: float | None = None
    t_dispatch: float | None = None
    t_verdict: float | None = None
    t_delivered: float | None = None
    routed: list[RoutedDocument] | None = None
    error: BaseException | None = None
    done: threading.Event = field(default_factory=threading.Event,
                                  repr=False)

    @property
    def latency_s(self) -> float | None:
        """Admission→delivery seconds (``None`` until delivered / if
        shed)."""
        if self.t_delivered is None:
            return None
        return self.t_delivered - self.t_submit

    @property
    def failed(self) -> bool:
        """Terminal failure: rejected, quarantined, or worker error."""
        return self.error is not None


@dataclass
class ReconfigTicket:
    """One live-reconfiguration request's ticket through the shadow
    builder: prepared off the hot path, committed by the completer at a
    batch boundary.  ``error`` set (and the live plan untouched) when
    the build or commit failed — the rollback path."""

    op: str                            # "subscribe" | "unsubscribe" | "rebalance"
    done: threading.Event = field(default_factory=threading.Event,
                                  repr=False)
    gid: int | None = None             # result for subscribe/unsubscribe
    stats: dict | None = None          # result for rebalance
    error: BaseException | None = None
    build_s: float = 0.0               # shadow build (prepare) seconds
    commit_s: float = 0.0              # atomic swap seconds


class ServeLoop:
    """Continuous serving front-end over a :class:`FilterStage`.

    Use as a context manager: exiting flushes the queue, drains all
    in-flight batches and joins the worker threads — a wedged device
    call is therefore visible as a *hanging close*, which is exactly
    what the CI serve job's timeout guards.

    ``deliver`` (optional) is called by the completer with each batch's
    routed documents, in order; a consumer that blocks inside it stalls
    the completer, which fills the K in-flight slots, which blocks the
    batcher, which fills the ingest queue, which sheds (or blocks) new
    arrivals — end-to-end backpressure with no unbounded buffer
    anywhere.
    """

    def __init__(self, stage: FilterStage, *, max_batch: int | None = None,
                 deadline_ms: float = 10.0, queue_cap: int = 64,
                 max_inflight: int = 2, overload: str = "shed",
                 deliver: Callable[[list[RoutedDocument]], Any] | None = None,
                 pad_batches: bool = True, validate: bool = True,
                 recover: bool = True, dead_letter_cap: int = 256,
                 rebalance_every_batches: int = 0,
                 rebalance_tolerance: float | None = None,
                 clock: Callable[[], float] = time.monotonic):
        if overload not in OVERLOAD_POLICIES:
            raise ValueError(f"overload must be one of {OVERLOAD_POLICIES}, "
                             f"got {overload!r}")
        if queue_cap < 1 or max_inflight < 1:
            raise ValueError("queue_cap and max_inflight must be >= 1")
        self.stage = stage
        self.max_batch = int(max_batch or stage.batch_size)
        self.deadline_s = float(deadline_ms) / 1e3
        self.queue_cap = int(queue_cap)
        self.max_inflight = int(max_inflight)
        self.overload = overload
        self.deliver = deliver
        # compiled-shape discipline: a deadline-closed undersized batch
        # is padded back to max_batch (repeating its last payload; the
        # pad rows' verdicts are sliced off) so the device program keeps
        # ONE batch shape — otherwise every distinct deadline-close size
        # triggers a fresh compile on the latency path.  Sparse stages
        # skip it (their match lists carry real doc ids).
        self.pad_batches = bool(pad_batches) and not stage.sparse
        #: reject known-bad bytes at submit() with a typed error, before
        #: they reach a kernel (host-side, vectorized — cheap)
        self.validate = bool(validate)
        #: isolate poison documents on batch failure (retry + bisection)
        #: instead of failing the whole batch; ``False`` marks all the
        #: batch's requests failed and keeps serving
        self.recover = bool(recover)
        self._max_depth = int(getattr(stage._eng, "max_depth",
                                      DEFAULT_MAX_DEPTH))
        #: run a shadow rebalance every N completed batches (0 = never)
        self.rebalance_every_batches = int(rebalance_every_batches)
        self.rebalance_tolerance = rebalance_tolerance
        self._clock = clock

        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._queue: deque[ServeRequest] = deque()
        self._closing = False
        self._closed = False
        self._error: BaseException | None = None
        # dispatched-but-undelivered batches are bounded at K: a slot is
        # taken at dispatch and released only after delivery
        self._slots = threading.Semaphore(self.max_inflight)
        self._comp_cv = threading.Condition()
        self._completion: deque = deque()
        self._latencies: list[float] = []
        self._batch_fills: list[float] = []
        #: bounded dead-letter buffer of quarantined documents: dicts of
        #: ``{seq, payload, error, message}`` (seq -1 = rejected at
        #: admission); oldest entries fall off at ``dead_letter_cap``
        self.dead_letter: deque[dict] = deque(maxlen=int(dead_letter_cap))
        #: committed hot swaps, in commit order: ``{op, build_s,
        #: commit_s, epoch}``
        self.swap_log: list[dict] = []
        self.counters = {"admitted": 0, "shed": 0, "completed": 0,
                         "batches": 0, "size_closes": 0,
                         "deadline_closes": 0, "flush_closes": 0,
                         "backpressure_waits": 0, "max_queue_depth": 0,
                         "rejected": 0, "quarantined": 0, "failed": 0,
                         "retries": 0, "swaps": 0, "swap_rollbacks": 0,
                         "delivery_errors": 0, "compiles": 0,
                         # seconds: spans of the batcher and completer,
                         # admission→dispatch summed over resolved
                         # requests, and backend compiles
                         "wait_arrival_s": 0.0, "wait_fill_s": 0.0,
                         "wait_slot_s": 0.0, "fan_out_s": 0.0,
                         "deliver_s": 0.0, "queue_s": 0.0,
                         "compile_s": 0.0}
        self._t_first: float | None = None
        self._t_last: float | None = None
        self._batches_since_rebalance = 0
        #: a payload the device path served, re-run to tell a poison
        #: document from a broken path (see :meth:`_recover`)
        self._last_good: bytes | None = None
        self._auto_ticket: ReconfigTicket | None = None
        self._reconfig_cv = threading.Condition()
        self._reconfig_q: deque = deque()

        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        self._pool = ThreadPoolExecutor(max_workers=self.max_inflight,
                                        thread_name_prefix="serve-filter")
        self._batcher_t = threading.Thread(target=self._batcher,
                                           name="serve-batcher", daemon=True)
        self._completer_t = threading.Thread(target=self._completer,
                                             name="serve-completer",
                                             daemon=True)
        self._builder_t = threading.Thread(target=self._builder,
                                           name="serve-plan-builder",
                                           daemon=True)
        self._batcher_t.start()
        self._completer_t.start()
        self._builder_t.start()

    # ------------------------------------------------------------- ingest
    def submit(self, payload: bytes) -> ServeRequest:
        """Admit one raw wire payload; returns its ticket immediately.

        Under overload (queue at ``queue_cap``): ``overload="shed"``
        marks the ticket shed and returns at once; ``"block"`` stalls
        the caller until the loop drains a slot (producer-side
        backpressure).  A loop that is closing sheds rather than
        deadlocking a blocked producer.

        With ``validate=True`` (default) known-bad bytes are *rejected*
        here — the ticket comes back with a typed
        :class:`~repro.core.events.DocumentError` and a dead-letter
        record, and the payload never reaches a kernel.
        """
        req = ServeRequest(payload=payload, t_submit=self._clock())
        if self.validate:
            try:
                validate_payload(payload, max_depth=self._max_depth)
            except DocumentError as e:
                req.error = e
                req.done.set()
                with self._lock:
                    self.counters["rejected"] += 1
                    self.counters["quarantined"] += 1
                    self.dead_letter.append(
                        {"seq": -1, "payload": payload,
                         "error": type(e).__name__, "message": str(e)})
                return req
        with self._lock:
            if self.overload == "shed":
                if len(self._queue) >= self.queue_cap or self._closing:
                    req.shed = True
                    self.counters["shed"] += 1
                    req.done.set()
                    return req
            else:
                while len(self._queue) >= self.queue_cap \
                        and not self._closing:
                    self._not_full.wait()
                if self._closing:
                    req.shed = True
                    self.counters["shed"] += 1
                    req.done.set()
                    return req
            req.seq = self.counters["admitted"]
            self.counters["admitted"] += 1
            if self._t_first is None:
                self._t_first = req.t_submit
            self._queue.append(req)
            depth = len(self._queue)
            if depth > self.counters["max_queue_depth"]:
                self.counters["max_queue_depth"] = depth
            self._not_empty.notify()
        return req

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    # ----------------------------------------------------------- batching
    def _batcher(self) -> None:
        try:
            while True:
                with self._lock:
                    # the waits are spans summed into the counters,
                    # under the lock the condition waits re-take
                    batch = self.counters["batches"]
                    with spans.span("xf.wait_arrival", self.counters,
                                    batch):
                        while not self._queue and not self._closing:
                            self._not_empty.wait()
                    if not self._queue and self._closing:
                        break
                    # batch opens now; close on size or deadline,
                    # whichever fires first (flush closes immediately)
                    deadline = self._clock() + self.deadline_s
                    with spans.span("xf.wait_fill", self.counters, batch):
                        while (len(self._queue) < self.max_batch
                               and not self._closing):
                            left = deadline - self._clock()
                            if left <= 0:
                                break
                            self._not_empty.wait(timeout=left)
                    n = min(self.max_batch, len(self._queue))
                    reqs = [self._queue.popleft() for _ in range(n)]
                    t_close = self._clock()
                    for r in reqs:
                        r.t_close = t_close
                    if n == self.max_batch:
                        self.counters["size_closes"] += 1
                    elif self._closing:
                        self.counters["flush_closes"] += 1
                    else:
                        self.counters["deadline_closes"] += 1
                    self.counters["batches"] += 1
                    self._not_full.notify_all()
                self._dispatch(reqs, batch)
        except BaseException as e:  # pragma: no cover - defensive
            self._fail(e)
        finally:
            with self._comp_cv:
                self._completion.append(None)
                self._comp_cv.notify()

    def _dispatch(self, reqs: list[ServeRequest], batch: int) -> None:
        """Take an in-flight slot (counting the wait as backpressure)
        and hand the batch to a worker; completion order is dispatch
        order regardless of which worker finishes first."""
        if not self._slots.acquire(blocking=False):
            with self._lock:
                self.counters["backpressure_waits"] += 1
            waited: dict = {}
            with spans.span("xf.wait_slot", waited, batch):
                self._slots.acquire()
            with self._lock:
                self.counters["wait_slot_s"] += waited["wait_slot_s"]
        t_dispatch = self._clock()
        for r in reqs:
            r.t_dispatch = t_dispatch
        future = self._pool.submit(self._run_batch,
                                   [r.payload for r in reqs], batch)
        with self._comp_cv:
            self._completion.append((reqs, future, batch))
            self._comp_cv.notify()

    def _run_batch(self, payloads: list[bytes], batch: int | None = None):
        """Worker-thread body: the stage's device bytes→verdict call.

        The batch is pinned to a :meth:`FilterStage.plan_epoch`
        snapshot — a hot swap committing mid-flight cannot tear
        engine/plan/gids — and the snapshot rides along for the
        epoch-consistent fan-out.  ``record=False`` — stage stats are
        mutated only by the single-threaded completer, so K concurrent
        workers never race the accounting dict.  ``batch`` names the
        spans the stage and engine open on this thread (the completer's
        re-runs keep the id it set); the clock reading once the match
        list is on the host is the batch's verdict time.
        """
        t0 = time.perf_counter()
        if batch is not None:
            spans.BATCH.set(batch)
        n = len(payloads)
        padded = payloads
        if self.pad_batches and n < self.max_batch:
            padded = payloads + [payloads[-1]] * (self.max_batch - n)
        ep = self.stage.plan_epoch()
        res = self.stage._filter_bytebatch(padded, record=False, epoch=ep)
        t_verdict = self._clock()
        if len(padded) != n:
            res = FilterResult(res.matched[:n], res.first_event[:n],
                               res.live)
        return (res, [len(p) for p in payloads], time.perf_counter() - t0,
                ep, t_verdict)

    # ----------------------------------------------------------- delivery
    def _completer(self) -> None:
        # two producers feed the completion queue: the batcher (batches)
        # and the shadow builder (plan swaps); each appends one None
        # sentinel on exit, and the completer drains until both are done
        # — so a swap enqueued during shutdown still commits
        producers = 2
        try:
            while True:
                with self._comp_cv:
                    while not self._completion:
                        self._comp_cv.wait()
                    item = self._completion.popleft()
                if item is None:
                    producers -= 1
                    if producers == 0:
                        break
                    continue
                if item[0] == "swap":
                    self._commit_swap(item[1], item[2], item[3])
                    continue
                reqs, future, batch = item
                # the completer's spans, and its re-runs of a failed
                # batch, carry the batch's id
                spans.BATCH.set(batch)
                try:
                    out = future.result()
                except BaseException as e:
                    # once the device path itself has failed, later
                    # batches fail fast instead of bisecting again
                    if self.recover and self._error is None:
                        self._recover(reqs, e)
                    else:
                        self._fail_requests(reqs, e)
                else:
                    self._resolve(reqs, *out)
                self._slots.release()
                self._maybe_auto_rebalance()
        except BaseException as e:  # pragma: no cover - defensive
            self._fail(e)

    def _resolve(self, reqs: list[ServeRequest], res, nbytes: list[int],
                 dt: float, ep: PlanEpoch, t_verdict: float) -> None:
        """Fan a finished batch's verdicts out to its tickets, then
        deliver them; latency runs from admission to the delivery's
        return.

        Routing uses the epoch the batch was *filtered* under
        (``ep.gids``) and the requests' own seqs — recovered subsets
        are non-contiguous, and a plan swapped after dispatch must not
        remap this batch's verdict columns."""
        c = self.counters
        with spans.span("xf.fan_out", c):
            routed = self.stage._fan_out(res, nbytes, gids=ep.gids,
                                         seqs=[r.seq for r in reqs])
            self.stage._record(res, len(reqs), sum(nbytes), dt)
        by_doc: dict[int, list[RoutedDocument]] = {}
        for rd in routed:
            by_doc.setdefault(rd.doc_index, []).append(rd)
        for r in reqs:
            r.t_verdict = t_verdict
            r.routed = by_doc.get(r.seq, [])
            c["queue_s"] += r.t_dispatch - r.t_submit
            r.done.set()
        c["completed"] += len(reqs)
        self._last_good = reqs[-1].payload
        self._batch_fills.append(len(reqs) / self.max_batch)
        if self.deliver is not None:
            # a stalled consumer stalls HERE, holding the slot: that is
            # the backpressure chain's first link.  A *raising* consumer
            # must not kill the loop — its error is counted, not fatal.
            with spans.span("xf.deliver", c):
                try:
                    self.deliver(routed)
                except BaseException:
                    c["delivery_errors"] += 1
        t_delivered = self._clock()
        for r in reqs:
            r.t_delivered = t_delivered
            self._latencies.append(t_delivered - r.t_submit)
        self._t_last = t_delivered

    # ------------------------------------------------- failure containment
    def _recover(self, reqs: list[ServeRequest], err: BaseException) -> None:
        """Contain a failed batch: isolate poison, save the rest — or
        fail the loop when the fault is not the documents' at all.

        Typed :class:`DocumentError`\\ s are quarantined as they come.
        Any other error gets one whole-batch retry (transient faults:
        worker hiccup, OOM race), then bisection: halves re-filter
        independently, and singletons that still fail are *suspects*.
        Suspects are quarantined as :class:`KernelFault` only when the
        fault is a document's — some subset of the batch was served, or
        a payload served earlier still is.  Otherwise every subset fails
        alike (a lowering, compile or device error): the requests fail
        with the raw error, which becomes the loop error that
        :meth:`close` re-raises, instead of a dead letter per request.
        """
        suspects: list[tuple[ServeRequest, Exception]] = []
        if _names_documents(err):
            served = self._isolate(reqs, err, suspects)
        else:
            self.counters["retries"] += 1
            served = self._try_subset(reqs, suspects)
        if not suspects:
            return
        settle = (self._quarantine if served or self._control_ok()
                  else self._fail_requests)
        for r, e in suspects:
            settle([r], e)

    def _isolate(self, reqs: list[ServeRequest], err: DocumentError,
                 suspects: list) -> bool:
        """Quarantine the documents a typed error names and re-filter
        the rest; returns whether any of the rest was served."""
        # pad rows repeat the last payload, so a pad-row index maps back
        # onto the last real request
        bad = {min(int(i), len(reqs) - 1) for i in err.doc_indices}
        self._quarantine([reqs[i] for i in sorted(bad)], err)
        rest = [r for i, r in enumerate(reqs) if i not in bad]
        return bool(rest) and self._try_subset(rest, suspects)

    def _try_subset(self, reqs: list[ServeRequest], suspects: list) -> bool:
        """Synchronously re-filter a subset on the completer thread,
        bisecting on failure; untyped singleton failures go to
        ``suspects`` as ``(request, error)``.  Returns whether any part
        of the subset was served."""
        try:
            out = self._run_batch([r.payload for r in reqs])
        except Exception as e:
            if _names_documents(e):
                return self._isolate(reqs, e, suspects)
            if len(reqs) == 1:
                suspects.append((reqs[0], e))
                return False
            mid = len(reqs) // 2
            left = self._try_subset(reqs[:mid], suspects)
            return self._try_subset(reqs[mid:], suspects) or left
        self._resolve(reqs, *out)
        return True

    def _control_ok(self) -> bool:
        """Does a payload that was served before still filter?  Tells a
        poison document (yes) from a broken device path (no, or nothing
        was ever served)."""
        if self._last_good is None:
            return False
        try:
            self._run_batch([self._last_good])
        except Exception:
            return False
        return True

    def _quarantine(self, reqs: list[ServeRequest],
                    err: BaseException) -> None:
        """Terminal poison state: typed error on each ticket (carrying
        the document's admission seq), bounded dead-letter record, loop
        keeps serving."""
        for r in reqs:
            if isinstance(err, DocumentError):
                e = type(err)(str(err), (r.seq,))
            else:
                e = KernelFault(f"{type(err).__name__}: {err}", (r.seq,))
            e.__cause__ = err if e is not err else None
            r.error = e
            with self._lock:
                self.counters["quarantined"] += 1
                self.dead_letter.append(
                    {"seq": r.seq, "payload": r.payload,
                     "error": type(e).__name__, "message": str(err)})
            r.done.set()

    def _fail_requests(self, reqs: Sequence[ServeRequest],
                       err: BaseException) -> None:
        """``recover=False`` terminal path: every request in the batch
        fails with the raw worker error; the loop keeps serving and
        ``close()`` re-raises the first such error."""
        with self._lock:
            if self._error is None:
                self._error = err
            self.counters["failed"] += len(reqs)
        for r in reqs:
            r.error = err
            r.done.set()

    def _fail(self, e: BaseException,
              reqs: Sequence[ServeRequest] = ()) -> None:
        with self._lock:
            if self._error is None:
                self._error = e
            self._not_full.notify_all()
        for r in reqs:
            r.error = e
            r.done.set()

    # ------------------------------------------------- shadow-plan hot swap
    def subscribe(self, profile, shard: int | None = None) -> ReconfigTicket:
        """Add a standing profile *live*: the replacement plan builds on
        the shadow builder thread and swaps in at a batch boundary — no
        queue drain, no filtering pause.  Wait on ``ticket.done`` for
        the gid (or the build error)."""
        return self._enqueue_reconfig("subscribe", profile, shard)

    def unsubscribe(self, gid: int) -> ReconfigTicket:
        """Drop a subscription live (shadow build + boundary swap)."""
        return self._enqueue_reconfig("unsubscribe", gid, None)

    def rebalance(self, tolerance: float | None = None) -> ReconfigTicket:
        """Shadow-rebalance the sharded plan; commits only if trie
        groups actually moved (``ticket.stats``)."""
        return self._enqueue_reconfig("rebalance", tolerance, None)

    def _enqueue_reconfig(self, op: str, arg, shard) -> ReconfigTicket:
        ticket = ReconfigTicket(op=op)
        with self._reconfig_cv:
            if self._closing:
                ticket.error = RuntimeError("serve loop is closing")
                ticket.done.set()
                return ticket
            self._reconfig_q.append((op, arg, shard, ticket))
            self._reconfig_cv.notify()
        return ticket

    def _builder(self) -> None:
        """Shadow-plan builder: one reconfiguration at a time, each
        prepared against the live epoch and handed to the completer for
        the atomic commit.  Serialized on ``ticket.done`` so the next
        prepare never races the previous commit (which would make it
        stale)."""
        try:
            while True:
                with self._reconfig_cv:
                    while not self._reconfig_q and not self._closing:
                        self._reconfig_cv.wait()
                    if not self._reconfig_q:
                        break            # closing, queue drained
                    op, arg, shard, ticket = self._reconfig_q.popleft()
                try:
                    if op == "subscribe":
                        pending = self.stage.prepare_subscribe(arg)
                    elif op == "unsubscribe":
                        pending = self.stage.prepare_unsubscribe(arg)
                    else:
                        pending = self.stage.prepare_rebalance(tolerance=arg)
                except BaseException as e:
                    # rollback: the live plan was never touched
                    ticket.error = e
                    with self._lock:
                        self.counters["swap_rollbacks"] += 1
                    ticket.done.set()
                    continue
                if pending is None:      # rebalance on an unsharded stage
                    ticket.done.set()
                    continue
                ticket.build_s = pending.build_s
                with self._comp_cv:
                    self._completion.append(("swap", ticket, pending, shard))
                    self._comp_cv.notify()
                ticket.done.wait()
        finally:
            with self._comp_cv:
                self._completion.append(None)
                self._comp_cv.notify()

    def _commit_swap(self, ticket: ReconfigTicket, pending,
                     shard) -> None:
        """Completer-side half of the hot swap: a few reference
        assignments under the stage's plan mutex, at a batch boundary
        (never mid-fan-out).  In-flight batches keep their dispatch
        epoch; the next ``_run_batch`` snapshot sees the new plan."""
        t0 = time.perf_counter()
        try:
            out = self.stage.commit(pending, shard=shard)
        except BaseException as e:
            ticket.error = e
            with self._lock:
                self.counters["swap_rollbacks"] += 1
        else:
            ticket.commit_s = time.perf_counter() - t0
            if pending.op == "rebalance":
                ticket.stats = out
            else:
                ticket.gid = out
            with self._lock:
                self.counters["swaps"] += 1
            self.swap_log.append(
                {"op": pending.op, "build_s": round(ticket.build_s, 6),
                 "commit_s": round(ticket.commit_s, 6),
                 "epoch": self.stage._epoch})
        ticket.done.set()

    def _maybe_auto_rebalance(self) -> None:
        """Traffic-driven rebalance: every N completed batches, kick a
        shadow rebalance (skipped while one is still in flight)."""
        if not self.rebalance_every_batches:
            return
        self._batches_since_rebalance += 1
        if self._batches_since_rebalance < self.rebalance_every_batches:
            return
        if self._auto_ticket is not None \
                and not self._auto_ticket.done.is_set():
            return
        self._batches_since_rebalance = 0
        self._auto_ticket = self.rebalance(self.rebalance_tolerance)

    # -------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Flush the queue, drain every in-flight batch and pending
        reconfiguration, join threads.  Idempotent and re-entrant: the
        second and later calls are no-ops (no re-join, no re-raise).

        Raises the first *loop* error, if any (an internal thread crash,
        or a batch failure under ``recover=False``) — exactly once.
        Quarantined documents are not loop errors: their typed
        exceptions live on their tickets and in :attr:`dead_letter`.
        """
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._closing = True
            self._not_empty.notify_all()
            self._not_full.notify_all()
        with self._reconfig_cv:
            self._reconfig_cv.notify_all()
        self._batcher_t.join()
        self._builder_t.join()
        self._completer_t.join()
        self._pool.shutdown(wait=True)
        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def __enter__(self) -> "ServeLoop":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def _on_compile(self, event: str, duration_s: float, **_kw) -> None:
        """``jax.monitoring`` listener: a backend compile, on whatever
        thread compiled, while the loop is open."""
        if event == COMPILE_EVENT:
            with self._lock:
                self.counters["compiles"] += 1
                self.counters["compile_s"] += duration_s

    # ------------------------------------------------------------ metrics
    def slo_summary(self) -> dict:
        """Admission→delivery latency percentiles + occupancy
        counters for everything served so far (ms; ``nan`` percentiles
        until something is delivered).

        Seconds summed by the spans: ``wait_arrival_s`` (batcher, queue
        empty), ``wait_fill_s`` (batch open, waiting for size or
        deadline), ``wait_slot_s`` (all in-flight slots taken),
        ``fan_out_s`` (completer: fan-out and the stage's accounting),
        ``deliver_s`` (the ``deliver`` callback); ``queue_s`` is
        admission→dispatch summed over resolved requests; ``compiles`` /
        ``compile_s`` count backend compiles while the loop is open.

        Accounting closes even under failures: every arrival ends in
        exactly one of completed / shed / failed / quarantined, so at
        quiescence ``arrived == completed + shed + failed +
        quarantined`` (``rejected`` — pre-admission — is the part of
        ``quarantined`` that never got a seq; ``arrived == admitted +
        shed + rejected``)."""
        lat_ms = np.asarray(self._latencies) * 1e3
        c = dict(self.counters)
        arrived = c["admitted"] + c["shed"] + c["rejected"]
        span = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        return {
            **c,
            "arrived": arrived,
            "shed_rate": c["shed"] / max(arrived, 1),
            "dead_letter_depth": len(self.dead_letter),
            "p50_ms": _pct(lat_ms, 50.0),
            "p99_ms": _pct(lat_ms, 99.0),
            "p999_ms": _pct(lat_ms, 99.9),
            "mean_ms": float(lat_ms.mean()) if lat_ms.size else float("nan"),
            "batch_fill": (float(np.mean(self._batch_fills))
                           if self._batch_fills else 0.0),
            "served_per_s": c["completed"] / span if span > 0 else 0.0,
        }

    def swap_summary(self) -> dict:
        """Hot-swap cost summary: shadow build vs atomic commit times
        (ms) over :attr:`swap_log` — the commit is the only part the
        latency path can ever observe."""
        builds = np.asarray([s["build_s"] for s in self.swap_log]) * 1e3
        commits = np.asarray([s["commit_s"] for s in self.swap_log]) * 1e3
        return {
            "swaps": self.counters["swaps"],
            "swap_rollbacks": self.counters["swap_rollbacks"],
            "build_p50_ms": _pct(builds, 50.0),
            "build_p99_ms": _pct(builds, 99.0),
            "commit_p50_ms": _pct(commits, 50.0),
            "commit_p99_ms": _pct(commits, 99.0),
        }

    def latencies_ms(self) -> np.ndarray:
        """Per-request admission→delivery latencies (ms), delivery
        order."""
        return np.asarray(self._latencies) * 1e3

    def latency_histogram(self, n_bins: int = 32) -> dict:
        """Log-spaced latency histogram — the CI artifact payload."""
        lat = self.latencies_ms()
        if lat.size == 0:
            return {"edges_ms": [], "counts": []}
        lo = max(float(lat.min()), 1e-3)
        hi = max(float(lat.max()), lo * (1 + 1e-6))
        edges = np.geomspace(lo, hi, n_bins + 1)
        counts, _ = np.histogram(lat, bins=edges)
        return {"edges_ms": edges.tolist(), "counts": counts.tolist()}


def _names_documents(err: BaseException) -> bool:
    """A typed document error that names the batch rows at fault."""
    return isinstance(err, DocumentError) and bool(err.doc_indices)


def _pct(xs: np.ndarray, q: float) -> float:
    return float(np.percentile(xs, q)) if xs.size else float("nan")


# ------------------------------------------------------- arrival traces
def poisson_arrivals(n: int, rate_hz: float, *, seed: int = 0) -> np.ndarray:
    """``n`` absolute arrival offsets (s) of a Poisson process."""
    if rate_hz <= 0:
        raise ValueError("rate_hz must be > 0")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_hz, size=n))

def burst_arrivals(n: int, rate_hz: float, *, on_s: float = 0.05,
                   off_s: float = 0.15, seed: int = 0) -> np.ndarray:
    """ON/OFF-modulated Poisson: bursts at ``rate_hz`` for ``on_s``,
    silence for ``off_s`` — the bursty-input scenario the paper's
    "very high input ratios" motivation describes.  Mean rate is
    ``rate_hz * on_s / (on_s + off_s)``."""
    if rate_hz <= 0:
        raise ValueError("rate_hz must be > 0")
    rng = np.random.default_rng(seed)
    out: list[float] = []
    t = 0.0
    while len(out) < n:
        window_end = t + on_s
        while len(out) < n:
            t += rng.exponential(1.0 / rate_hz)
            if t >= window_end:
                break
            out.append(t)
        t = window_end + off_s
    return np.asarray(out[:n])

def replay_arrivals(n: int, rate_hz: float | None = None) -> np.ndarray:
    """Deterministic trace: back-to-back (``rate_hz=None``) or evenly
    spaced at ``rate_hz`` — replaying a fixed request list through the
    loop (the old batch driver's arrival pattern, as a trace)."""
    if rate_hz is None or rate_hz <= 0:
        return np.zeros(n)
    return np.arange(n, dtype=np.float64) / rate_hz


def make_arrivals(kind: str, n: int, *, rate_hz: float,
                  on_s: float = 0.05, off_s: float = 0.15,
                  seed: int = 0) -> np.ndarray:
    """Trace dispatcher for the CLI/bench ``--arrival`` knob."""
    if kind == "poisson":
        return poisson_arrivals(n, rate_hz, seed=seed)
    if kind == "burst":
        return burst_arrivals(n, rate_hz, on_s=on_s, off_s=off_s, seed=seed)
    if kind == "replay":
        return replay_arrivals(n, rate_hz)
    raise ValueError(f"unknown arrival trace {kind!r} "
                     f"(poisson|burst|replay)")


def run_trace(loop: ServeLoop, payloads: Sequence[bytes],
              arrivals: np.ndarray, *,
              clock: Callable[[], float] = time.monotonic,
              sleep: Callable[[float], Any] = time.sleep
              ) -> list[ServeRequest]:
    """Submit ``payloads[i]`` at offset ``arrivals[i]`` (open-loop: the
    trace does NOT slow down when the service falls behind, which is
    what makes shed/backpressure measurable).  Returns the tickets."""
    if len(payloads) != len(arrivals):
        raise ValueError(f"{len(payloads)} payloads vs "
                         f"{len(arrivals)} arrival offsets")
    t0 = clock()
    tickets = []
    for payload, due in zip(payloads, arrivals):
        lag = due - (clock() - t0)
        if lag > 0:
            sleep(lag)
        tickets.append(loop.submit(payload))
    return tickets
