"""Pub-sub content routing as a data-pipeline stage.

This is where the paper's contribution is a *first-class feature* of the
framework: a stream of XML documents is matched against standing profiles
(subscriptions) and routed — exactly the paper's pub-sub filtering — as a
stage in front of the training/serving data pipeline:

* training: documents are filtered by topic profiles and routed to
  data-parallel shards (``launch/train.py --data-filter``);
* serving: requests carrying XML payloads are routed to model replicas by
  subscription (``launch/serve.py``).

The stage is engine-agnostic: any registered engine name
(:func:`repro.core.engines.names`) works, because every engine consumes
the same :class:`~repro.core.events.EventBatch` and returns the same
batched ``(B, Q)`` :class:`~repro.core.engines.FilterResult`.  Batches
are padded to bucket boundaries so the number of compiled shapes stays
bounded, and ``stage.stats`` accumulates per-batch throughput and
selectivity.

Two ingest paths feed the same router: :meth:`FilterStage.route` takes
pre-parsed event streams (host parse), :meth:`FilterStage.route_bytes`
takes raw paper-format byte payloads and parses them *on device*
(:func:`repro.kernels.parse.parse_batch` / the engine's fused
``filter_bytes``) — the paper's same-chip parser+filter dataflow.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Iterable, Iterator, Sequence

import numpy as np

from ..core import engines
from ..core.dictionary import TagDictionary
from ..core.engines import FilterResult, SparseResult
from ..core.events import (ByteBatch, EventBatch, EventStream,
                           event_stream_nbytes)
from ..core.nfa import NFA, compile_queries
from ..core.spans import span
from ..core.xpath import Query, parse

TEXT_FILL = 8  # filler text bytes per element in the MB/s accounting
#: host spans of one batch's worker path (:mod:`repro.core.spans`), carried
#: in a sparse result's ``meta`` and summed into ``stats`` by ``_record``
SPAN_KEYS = ("pack_s", "launch_s", "device_s", "expand_s")


@dataclass
class RoutedDocument:
    doc_index: int
    matched_profiles: np.ndarray       # (n_matched,) int32 profile indices
    shard: int                         # destination data shard
    nbytes: int


class StalePlanError(RuntimeError):
    """A prepared plan's base epoch no longer matches the live plan.

    Raised by :meth:`FilterStage.commit` when another commit landed
    between ``prepare_*`` and ``commit`` — the pending plan was built
    against a subscription set that no longer exists.  The caller
    re-prepares against the current plan (the synchronous churn methods
    do this automatically; the serve loop's shadow builder records it as
    a rollback)."""


@dataclass
class PlanEpoch:
    """Immutable snapshot of the live plan, taken at dispatch time.

    A batch dispatched against epoch *E* filters with *E*'s engine,
    sharded plan and gid mapping even if churn commits a replacement
    mid-flight — verdict columns and the gid axis always agree, which is
    what makes the serve loop's shadow-plan hot swap safe with in-flight
    batches (no queue drain)."""

    epoch: int
    eng: Any
    sharded: Any                       # ShardedPlan | None
    gids: np.ndarray


@dataclass
class PendingPlan:
    """A fully built replacement plan awaiting an atomic commit.

    Produced off the hot path by ``prepare_subscribe`` /
    ``prepare_unsubscribe`` / ``prepare_rebalance`` — all the expensive
    work (NFA compile, part re-plan, rebalance migration) happens during
    *prepare*, against a snapshot, without mutating the stage; ``commit``
    is a handful of reference assignments under the plan mutex."""

    op: str                            # "subscribe" | "unsubscribe" | "rebalance"
    base_epoch: int
    gid: int | None = None
    stats: dict | None = None          # rebalance stats
    sharded: Any = None                # replacement ShardedPlan
    eng: Any = None                    # replacement engine (unsharded path)
    nfa: Any = None
    live: dict | None = None
    gids: np.ndarray | None = None
    build_s: float = 0.0


@dataclass
class FilterStage:
    """Standing-profile filter + router over any registered engine.

    ``shard_of_profile[q]`` maps each subscription to a destination shard
    (defaults to round-robin).  A document goes to every shard that has at
    least one matching subscription; unmatched documents are dropped
    (classic pub-sub) or sent to shard 0 with ``keep_unmatched=True``.

    ``bucket`` controls padded-batch bucketing: each batch's event axis is
    padded to the next multiple, capping the number of distinct shapes
    the device engines compile for; ``byte_bucket`` does the same for the
    raw-byte axis of the device-ingest path (:meth:`route_bytes`).

    ``query_shards > 1`` partitions the subscription set into that many
    balanced parts (:meth:`FilterEngine.plan_sharded`) and filters
    through the sharded path — all parts in one stacked device program,
    spread over the mesh ``"model"`` axis (auto-built when none is
    given, shrunk to what the host can place).  Routing
    is by **global query id** through the partition index, so documents
    fan out to data shards identically with and without query sharding.
    Subscriptions can then churn live: :meth:`subscribe` recompiles only
    the least-loaded part, :meth:`unsubscribe` is pure metadata.

    ``data_shards > 1`` adds the second scaling axis: batches run
    through the 2-D ``("data", "model")`` program
    (:meth:`FilterEngine.filter_batch_sharded2d`), documents spread over
    the mesh ``"data"`` axis while each device keeps its 1/P slice of
    the queries — the paper's §3.5 replication in both dimensions.  A
    mesh is built automatically when none is given.  The bytes path gets
    an async double-buffered serve loop on top:
    :meth:`route_bytes_pipelined` overlaps the ``jax.device_put`` of
    batch *k+1* with the filter step still running on batch *k*.
    """

    profiles: Sequence[Query]
    dictionary: TagDictionary
    n_shards: int = 1
    engine: str = "levelwise"
    keep_unmatched: bool = False
    batch_size: int = 32
    bucket: int = 128
    byte_bucket: int = 1024
    query_shards: int = 1
    data_shards: int = 1
    #: in-flight depth of :meth:`route_bytes_pipelined` — how many
    #: dispatched-but-unmaterialized batches the loop keeps (2 = the
    #: classic double buffer; the serve loop raises it via its own
    #: ``max_inflight``)
    pipeline_depth: int = 2
    mesh: Any = None
    shard_of_profile: np.ndarray = field(default=None)  # type: ignore
    stats: dict = field(default_factory=dict)
    #: deliver verdicts as sparse (doc, gid) match lists — the bounded
    #: device match buffer instead of the dense (B, Q) bitmap (engines'
    #: ``filter_batch_sparse`` family); routing output is identical
    sparse: bool = False
    #: run :meth:`maybe_rebalance` automatically every N churn ops
    #: (0 = manual only); ``rebalance_tolerance`` is the max/mean-1
    #: imbalance the plan is allowed before groups migrate
    rebalance_every: int = 0
    rebalance_tolerance: float = 0.25
    #: extra engine options (e.g. ``{"minimize": True}`` for global NFA
    #: minimization, ``{"match_cap": ...}`` for the sparse buffer bound)
    engine_options: dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        if isinstance(self.profiles[0], str):
            self.profiles = [parse(p) for p in self.profiles]
        # live subscription set, keyed by stable global query id;
        # ids are never reused (monotonic counter), matching ShardedPlan
        self._live: dict[int, Query] = dict(enumerate(self.profiles))
        self._next_gid = len(self.profiles)
        self._gids = np.arange(len(self.profiles), dtype=np.int32)
        self.nfa: NFA = compile_queries(list(self.profiles), self.dictionary,
                                        shared=True)
        # event_bucket threads this stage's padding bucket into every
        # engine byte path, so a call that omits bucket= can never fall
        # back to a different (hard-coded) boundary than the stage's own
        self._eng = engines.create(self.engine, self.nfa,
                                   dictionary=self.dictionary,
                                   event_bucket=self.bucket,
                                   **self.engine_options)
        self._churn_ops = 0
        if (self.query_shards > 1 or self.data_shards > 1) \
                and self.mesh is None:
            from ..launch.mesh import make_filter_mesh
            # n_parts caps the model axis at the part count (a monolithic
            # plan gets a 1-wide model axis, all devices on "data")
            self.mesh = make_filter_mesh(max(1, self.query_shards),
                                         data_shards=self.data_shards)
        # the data axis needs a sharded plan even with one query part
        # (the 2-D program executes a stacked ShardedPlan)
        self.sharded_ = (self._eng.plan_sharded(max(1, self.query_shards))
                         if self.query_shards > 1 or self.data_shards > 1
                         else None)
        if self.shard_of_profile is None:
            self.shard_of_profile = (
                np.arange(len(self.profiles)) % self.n_shards).astype(np.int32)
        self.stats = {"batches": 0, "docs": 0, "bytes": 0,
                      "seconds": 0.0, "pair_matches": 0, "pairs": 0,
                      "put_seconds": 0.0, "overlapped_batches": 0,
                      "verdict_bytes": 0, "rebalances": 0,
                      # tag starts the bytes kernel walked and the
                      # chunks it fetched from HBM (``meta``)
                      "tag_starts": 0, "stream_chunks": 0,
                      **dict.fromkeys(SPAN_KEYS, 0.0),
                      # sparse batches per engine route (``meta["path"]``)
                      "verdict_paths": {}}
        # plan epoch: bumped on every committed plan change; the mutex
        # covers only snapshot/commit (reference assignments), never a
        # compile — prepare_* does the expensive work outside it
        self._plan_mtx = threading.Lock()
        self._epoch = 0

    # --------------------------------------------------- subscription churn
    def plan_epoch(self) -> PlanEpoch:
        """Consistent (epoch, engine, plan, gids) snapshot for dispatch.

        A batch filtered against this snapshot and fanned out with its
        ``gids`` is correct even if a plan swap commits while the batch
        is in flight."""
        with self._plan_mtx:
            return PlanEpoch(self._epoch, self._eng, self.sharded_,
                             self._gids)

    def prepare_subscribe(self, profile: Query | str) -> PendingPlan:
        """Build (but do not install) the plan that adds ``profile``.

        Pure with respect to the stage: sharded stages re-plan only the
        least-loaded part (:meth:`ShardedPlan.add_queries`), unsharded
        stages compile the full replacement engine — either way against
        a snapshot, so a failed build (e.g. a rejected profile) leaves
        the live plan untouched with nothing to roll back."""
        q = parse(profile) if isinstance(profile, str) else profile
        t0 = time.perf_counter()
        with self._plan_mtx:
            base = self._epoch
            sharded = self.sharded_
            live = dict(self._live)
            gid = self._next_gid
        if sharded is not None:
            sp, new = sharded.add_queries([q])
            gid = new[0]
            live[gid] = q
            return PendingPlan("subscribe", base, gid=gid, sharded=sp,
                               live=live, gids=sp.live_ids(),
                               build_s=time.perf_counter() - t0)
        live[gid] = q
        gids = sorted(live)
        nfa = compile_queries([live[g] for g in gids], self.dictionary,
                              shared=True)
        eng = engines.create(self.engine, nfa, dictionary=self.dictionary,
                             event_bucket=self.bucket, **self.engine_options)
        return PendingPlan("subscribe", base, gid=gid, eng=eng, nfa=nfa,
                           live=live, gids=np.asarray(gids, np.int32),
                           build_s=time.perf_counter() - t0)

    def prepare_unsubscribe(self, gid: int) -> PendingPlan:
        """Build the plan that drops ``gid`` (tombstone when sharded)."""
        if gid not in self._live:
            raise KeyError(f"query id {gid} is not subscribed")
        t0 = time.perf_counter()
        with self._plan_mtx:
            base = self._epoch
            sharded = self.sharded_
            live = dict(self._live)
        del live[gid]
        if sharded is not None:
            sp = sharded.remove_queries([gid])
            return PendingPlan("unsubscribe", base, gid=gid, sharded=sp,
                               live=live, gids=sp.live_ids(),
                               build_s=time.perf_counter() - t0)
        gids = sorted(live)
        nfa = compile_queries([live[g] for g in gids], self.dictionary,
                              shared=True)
        eng = engines.create(self.engine, nfa, dictionary=self.dictionary,
                             event_bucket=self.bucket, **self.engine_options)
        return PendingPlan("unsubscribe", base, gid=gid, eng=eng, nfa=nfa,
                           live=live, gids=np.asarray(gids, np.int32),
                           build_s=time.perf_counter() - t0)

    def prepare_rebalance(self, *, tolerance: float | None = None
                          ) -> PendingPlan | None:
        """Build the rebalanced plan (sharded stages only, else None).

        ``pending.sharded`` is ``None`` when no trie groups needed to
        move — committing such a plan is a no-op that still returns the
        stats."""
        if self.sharded_ is None:
            return None
        tol = (self.rebalance_tolerance
               if tolerance is None else tolerance)
        t0 = time.perf_counter()
        with self._plan_mtx:
            base = self._epoch
            sharded = self.sharded_
        new, stats = sharded.rebalance(tolerance=tol)
        moved = bool(stats["moves"])
        return PendingPlan("rebalance", base, stats=stats,
                           sharded=new if moved else None,
                           gids=new.live_ids() if moved else None,
                           build_s=time.perf_counter() - t0)

    def commit(self, pending: PendingPlan, shard: int | None = None):
        """Atomically install a prepared plan at the current epoch.

        A handful of reference assignments under the plan mutex —
        batches dispatched against the previous :meth:`plan_epoch`
        snapshot keep filtering the old plan; the next snapshot sees the
        new one.  Raises :class:`StalePlanError` (leaving the live plan
        untouched) if another commit landed since ``prepare_*``.
        Returns the gid for churn ops, the stats dict for rebalances."""
        with self._plan_mtx:
            if pending.base_epoch != self._epoch:
                raise StalePlanError(
                    f"plan prepared against epoch {pending.base_epoch}, "
                    f"live plan is at {self._epoch}; re-prepare")
            if pending.op == "rebalance":
                if pending.sharded is not None:
                    self.sharded_ = pending.sharded
                    self._gids = pending.gids
                    self.stats["rebalances"] += 1
                    self._epoch += 1
                return pending.stats
            self._live = pending.live
            if pending.sharded is not None:
                self.sharded_ = pending.sharded
            else:
                self.nfa = pending.nfa
                self._eng = pending.eng
            self._gids = pending.gids
            self._epoch += 1
            if pending.op == "subscribe":
                self._next_gid = max(self._next_gid, pending.gid + 1)
                self._grow_shard_map(pending.gid, shard)
            return pending.gid

    def subscribe(self, profile: Query | str, shard: int | None = None) -> int:
        """Add a standing profile live; returns its global query id.

        Sharded stages recompile only the least-loaded part
        (:meth:`ShardedPlan.add_queries`); unsharded stages pay the full
        recompile — the cost gap is the point of query sharding.
        Prepare/commit under the hood: a failed build never touches the
        live plan, and a concurrent commit just means one re-prepare.
        """
        while True:
            pending = self.prepare_subscribe(profile)
            try:
                gid = self.commit(pending, shard=shard)
                break
            except StalePlanError:
                continue
        self._after_churn()
        return gid

    def unsubscribe(self, gid: int) -> None:
        """Remove a subscription by global id (live, no re-plan when
        sharded — the column is tombstoned)."""
        while True:
            pending = self.prepare_unsubscribe(gid)
            try:
                self.commit(pending)
                break
            except StalePlanError:
                continue
        self._after_churn()

    def _after_churn(self) -> None:
        self._churn_ops += 1
        if (self.rebalance_every
                and self._churn_ops >= self.rebalance_every):
            self._churn_ops = 0
            self.maybe_rebalance()

    def maybe_rebalance(self, *, tolerance: float | None = None
                        ) -> dict | None:
        """Off-hot-path shard-load repair (sharded stages only).

        Runs :meth:`ShardedPlan.rebalance` against the live plan and, if
        any trie groups moved, swaps the new frozen plan in with a
        single reference assignment — batches already dispatched keep
        filtering the old plan, the next batch picks up the new one, and
        verdicts/routing are identical either way (the rebalance
        invariant).  Returns the rebalance stats, or ``None`` when the
        stage is unsharded.
        """
        while True:
            pending = self.prepare_rebalance(tolerance=tolerance)
            if pending is None:
                return None
            try:
                return self.commit(pending)
            except StalePlanError:
                continue

    def _grow_shard_map(self, gid: int, shard: int | None) -> None:
        if gid >= len(self.shard_of_profile):
            extra = np.arange(len(self.shard_of_profile), gid + 1)
            self.shard_of_profile = np.concatenate(
                [self.shard_of_profile,
                 (extra % self.n_shards).astype(np.int32)])
        if shard is not None:
            self.shard_of_profile[gid] = shard

    # ----------------------------------------------------------------- run
    def _filter_batch(self, docs: list[EventStream],
                      record: bool = True) -> FilterResult:
        """Uniform batched path: every engine gets one EventBatch and
        returns one (B, Q) FilterResult.  ``record=False`` keeps
        metric-only reads (e.g. :meth:`selectivity`) out of the
        cumulative routing stats."""
        batch = EventBatch.from_streams(docs, bucket=self.bucket)
        t0 = time.perf_counter()
        if self.data_shards > 1:
            res = (self._eng.filter_batch_sharded2d_sparse if self.sparse
                   else self._eng.filter_batch_sharded2d)(
                       batch, self.sharded_, mesh=self.mesh)
        elif self.sharded_ is not None:
            res = (self._eng.filter_batch_sharded_sparse if self.sparse
                   else self._eng.filter_batch_sharded)(
                       batch, self.sharded_, mesh=self.mesh)
        elif self.sparse:
            res = self._eng.filter_batch_sparse(batch)
        else:
            res = self._eng.filter_batch(batch)
        dt = time.perf_counter() - t0
        if record:
            self._record(res, batch.batch_size,
                         int(batch.nbytes(TEXT_FILL).sum()), dt)
        return res

    def _record(self, res: FilterResult | SparseResult, n_docs: int,
                n_bytes: int, dt: float) -> None:
        """One accounting path for both ingest forms, so throughput()
        stays comparable between them."""
        self.stats["batches"] += 1
        self.stats["docs"] += n_docs
        self.stats["bytes"] += n_bytes
        self.stats["seconds"] += dt
        if isinstance(res, SparseResult):
            self.stats["pair_matches"] += res.n_matches
            self.stats["pairs"] += res.batch_size * res.n_live
            self.stats["verdict_bytes"] += res.verdict_bytes
            paths = self.stats["verdict_paths"]
            path = res.meta.get("path")
            paths[path] = paths.get(path, 0) + 1
            self.stats["tag_starts"] += res.meta.get("tag_starts", 0)
            self.stats["stream_chunks"] += res.meta.get("stream_chunks", 0)
            for k in SPAN_KEYS:
                self.stats[k] += res.meta.get(k, 0.0)
        else:
            self.stats["pair_matches"] += int(res.matched.sum())
            self.stats["pairs"] += res.matched.size
            self.stats["verdict_bytes"] += res.matched.size * 5

    def _filter_bytebatch(self, bufs: list[bytes], record: bool = True,
                          epoch: PlanEpoch | None = None) -> FilterResult:
        """Device-ingest batched path: raw wire bytes in, ``(B, Q)``
        verdicts out, parsed on device by ``engine.filter_bytes`` — no
        per-event host Python between payload and verdict.  ``epoch``
        pins the batch to a :meth:`plan_epoch` snapshot so a concurrent
        plan swap cannot tear engine/plan/gids mid-batch.  The packing
        is the ``xf.pack`` span; a sparse result carries it in ``meta``
        beside the engine's own spans."""
        eng = self._eng if epoch is None else epoch.eng
        sharded = self.sharded_ if epoch is None else epoch.sharded
        spans: dict = {}
        with span("xf.pack", spans):
            bb = ByteBatch.from_buffers(bufs, bucket=self.byte_bucket)
        t0 = time.perf_counter()
        if self.data_shards > 1:
            res = eng.filter_bytes_sharded2d(bb, sharded,
                                             bucket=self.bucket,
                                             mesh=self.mesh)
            if self.sparse:
                res = res.sparsify(sharded.live_ids())
                res.meta["path"] = "dense-2d"
        elif sharded is not None:
            res = (eng.filter_bytes_sharded_sparse if self.sparse
                   else eng.filter_bytes_sharded)(
                       bb, sharded, bucket=self.bucket,
                       mesh=self.mesh)
        elif self.sparse:
            res = eng.filter_bytes_sparse(bb, bucket=self.bucket)
        else:
            res = eng.filter_bytes(bb, bucket=self.bucket)
        dt = time.perf_counter() - t0
        if isinstance(res, SparseResult):
            res.meta.update(spans)
        if record:
            self._record(res, bb.batch_size, bb.nbytes_total(), dt)
        return res

    def _chunks(self, items: Iterable) -> Iterator[list]:
        """Accumulate an (unbounded) iterable into batch_size chunks —
        the one batching loop all three routing paths share."""
        batch: list = []
        for item in items:
            batch.append(item)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch:
            yield batch

    def route(self, docs: Iterable[EventStream]) -> Iterator[list[RoutedDocument]]:
        """Yield routed batches; each doc may fan out to several shards."""
        base = 0
        for batch in self._chunks(docs):
            yield self._route_batch(batch, base)
            base += len(batch)

    def route_bytes(self, payloads: Iterable[bytes]
                    ) -> Iterator[list[RoutedDocument]]:
        """Route raw paper-format byte payloads (device-ingest twin of
        :meth:`route`): each batch is parsed *and* filtered on device,
        then fanned out to shards exactly like the event path."""
        base = 0
        for batch in self._chunks(payloads):
            yield self._route_byte_batch(batch, base)
            base += len(batch)

    # ------------------------------------------- double-buffered serve loop
    def _stage_in(self, bufs: list[bytes]):
        """Host-side staging of one batch: pack, take the event bound
        (a host metadata scan — done BEFORE placement so the device copy
        is never read back), then issue the async ``device_put`` against
        the mesh ``"data"`` axis."""
        bb = ByteBatch.from_buffers(bufs, bucket=self.byte_bucket)
        n_events = bb.event_bound(bucket=self.bucket)
        t0 = time.perf_counter()
        placed = bb.device_put(self.mesh)
        # device_put is async: this times dispatch, not the transfer —
        # the transfer itself overlaps the previous batch's filter step
        self.stats["put_seconds"] += time.perf_counter() - t0
        return bufs, bb, placed, n_events

    def _dispatch_byte_batch(self, bufs: list[bytes]):
        """Stage one raw-byte batch (exactly once — ``put_seconds``
        counts each batch's ``device_put`` dispatch a single time) and
        launch the async 2-D bytes→verdict program.  Returns the
        in-flight entry the K-deep loop materializes later."""
        bufs, bb, placed, n_events = self._stage_in(bufs)
        t0 = time.perf_counter()
        materialize = self._eng.dispatch_bytes_sharded2d(
            placed, self.sharded_, mesh=self.mesh, n_events=n_events)
        return bufs, bb, materialize, t0

    def _materialize_routed(self, entry, base: int) -> list[RoutedDocument]:
        """Block on one in-flight batch's verdicts, account, fan out."""
        bufs, bb, materialize, t0 = entry
        res = materialize()
        # slice off data-axis pad rows before accounting/fan-out
        res = FilterResult(res.matched[:len(bufs)],
                           res.first_event[:len(bufs)])
        self._record(res, bb.batch_size, bb.nbytes_total(),
                     time.perf_counter() - t0)
        return self._fan_out(res, [len(b) for b in bufs], base)

    def route_bytes_pipelined(self, payloads: Iterable[bytes], *,
                              depth: int | None = None
                              ) -> Iterator[list[RoutedDocument]]:
        """K-deep pipelined twin of :meth:`route_bytes` for the 2-D
        mesh: while the bytes→verdict program runs on batch *k*, up to
        ``depth - 1`` successor batches are already packed, their H2D
        transfers in flight and their filter programs dispatched.

        Per batch: (1) stage (pack + async ``ByteBatch.device_put``) and
        dispatch the 2-D filter program
        (:meth:`FilterEngine.dispatch_bytes_sharded2d` — asynchronous,
        returns a materializer); (2) once ``depth`` batches are in
        flight, block on the *oldest* one's verdicts and fan out (FIFO —
        routed order is identical to :meth:`route_bytes`).  ``depth``
        defaults to :attr:`pipeline_depth` (2 = the classic double
        buffer); the serve loop passes its own ``max_inflight``.  Each
        batch is staged exactly once, so ``put_seconds`` accounts every
        ``device_put`` dispatch a single time at any depth.  Throughput
        and overlap accounting land in ``stats`` (``put_seconds``,
        ``overlapped_batches``).  Falls back to :meth:`route_bytes`
        when the stage has no mesh to overlap against.
        """
        if self.mesh is None or self.sharded_ is None:
            yield from self.route_bytes(payloads)
            return
        k = max(1, self.pipeline_depth if depth is None else depth)

        # streaming K-deep window: only the k in-flight batches are
        # ever held — an unbounded payload stream yields verdicts batch
        # by batch, exactly like route_bytes
        inflight: deque = deque()
        base = 0
        for bufs in self._chunks(payloads):
            if inflight:
                # a predecessor's filter step is still in flight while
                # this batch stages: the overlap the pipeline exists for
                self.stats["overlapped_batches"] += 1
            inflight.append(self._dispatch_byte_batch(bufs))
            if len(inflight) >= k:
                entry = inflight.popleft()
                yield self._materialize_routed(entry, base)
                base += len(entry[0])
        while inflight:
            entry = inflight.popleft()
            yield self._materialize_routed(entry, base)
            base += len(entry[0])

    def _route_batch(self, docs: list[EventStream],
                     base: int) -> list[RoutedDocument]:
        results = self._filter_batch(docs)
        return self._fan_out(results, [event_stream_nbytes(d) for d in docs],
                             base)

    def _route_byte_batch(self, bufs: list[bytes],
                          base: int) -> list[RoutedDocument]:
        results = self._filter_bytebatch(bufs)
        return self._fan_out(results, [len(b) for b in bufs], base)

    def _fan_out(self, results: FilterResult | SparseResult,
                 nbytes: list[int], base: int = 0, *,
                 gids: np.ndarray | None = None,
                 seqs: Sequence[int] | None = None) -> list[RoutedDocument]:
        """Verdicts → routed documents.  ``gids`` pins the live-column →
        global-id mapping to the epoch the batch was filtered under
        (defaults to the current plan); ``seqs`` assigns explicit,
        possibly non-contiguous document indices (the serve loop's
        quarantine retries filter recovered subsets whose seqs are not
        ``base + i``)."""
        sparse = isinstance(results, SparseResult)
        live = self._gids if gids is None else gids
        out: list[RoutedDocument] = []
        for i, nb in enumerate(nbytes):
            doc = base + i if seqs is None else int(seqs[i])
            # result columns are live-query columns; route by global id
            # through the partition index so churn/sharding never change
            # which data shard a profile delivers to.  Sparse producers
            # with live_ids already speak global ids.
            if sparse:
                qids = results.matching_queries(i)
                if results.live_ids is None:
                    qids = live[qids]
            else:
                qids = live[results[i].matching_queries()]
            if len(qids) == 0:
                if self.keep_unmatched:
                    out.append(RoutedDocument(doc, qids, 0, nb))
                continue
            for shard in np.unique(self.shard_of_profile[qids]):
                mine = qids[self.shard_of_profile[qids] == shard]
                out.append(RoutedDocument(doc, mine, int(shard), nb))
        return out

    # ------------------------------------------------------------- metrics
    def selectivity(self, docs: list[EventStream]) -> float:
        """Fraction of (doc, profile) pairs that match — workload stat.

        Read-only: does not count toward :meth:`throughput`."""
        return self._filter_batch(list(docs), record=False).selectivity()

    def throughput(self) -> dict:
        """Cumulative filtering throughput over everything routed so far.

        Per-axis view: ``mesh_data``/``mesh_model`` are the *placed*
        mesh axis sizes (the requested shard counts shrink to what the
        host can place — see ``make_filter_mesh``);
        ``docs_per_s_per_data_shard`` is each document replica's share
        of the stream, and ``queries_per_model_shard`` each device's
        slice of the subscription set.
        """
        s = self.stats
        dt = max(s["seconds"], 1e-9)
        axes = dict(self.mesh.shape) if self.mesh is not None else {}
        mesh_data = axes.get("data", 1)
        mesh_model = axes.get("model", 1)
        n_live = len(self._gids)
        return {
            "engine": self.engine,
            "query_shards": self.query_shards,
            "data_shards": self.data_shards,
            "mesh_data": mesh_data,
            "mesh_model": mesh_model,
            "docs": s["docs"],
            "docs_per_s": s["docs"] / dt,
            "docs_per_s_per_data_shard": s["docs"] / dt / mesh_data,
            "queries_per_model_shard": -(-n_live // max(mesh_model, 1)),
            "mb_per_s": s["bytes"] / 1e6 / dt,
            "put_s": s["put_seconds"],
            "overlapped_batches": s["overlapped_batches"],
            "selectivity": s["pair_matches"] / max(s["pairs"], 1),
        }
