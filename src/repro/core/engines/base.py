"""The engine contract: ``FilterPlan`` + ``FilterEngine`` + the registry.

This is the single seam of the filtering stack.  The paper's architecture
(§3) compiles the standing profiles once into hardware blocks and then
streams every document through the same fixed datapath; the software
analogue is:

* :class:`FilterPlan` — the compiled form: a *frozen pytree* of
  precomputed device tables (REQ / parent-one-hot / accept matrices,
  packed init words, …) plus static metadata.  Built once per profile
  set by :meth:`FilterEngine.plan`; every ``filter_batch`` call reuses
  it, so tracing/compilation happens once and the plan can be passed
  through ``jax.jit`` boundaries as an ordinary pytree argument.
* :class:`FilterEngine` — the uniform engine interface: ``plan(nfa)``
  and ``filter_batch(EventBatch) -> FilterResult`` with ``(B, Q)``
  outputs.  Engines are free to run on device (streaming, levelwise,
  matscan) or on the host (oracle, yfilter) — callers cannot tell.
* the **registry** — engines self-register under a string key;
  ``engines.get("levelwise")`` / ``engines.create("levelwise", nfa)``
  is how every pipeline, benchmark and example constructs one, so an
  engine comparison is a flag, not an import.

Adding an engine::

    from repro.core.engines import base

    @base.register("myengine")
    class MyEngine(base.FilterEngine):
        def plan(self, nfa):
            return base.FilterPlan("myengine",
                                   tables={"req": jnp.asarray(...)},
                                   meta={"n_states": nfa.n_states})
        def filter_batch(self, batch):
            ...
"""
from __future__ import annotations

import abc
import dataclasses
import hashlib
import json
import os
from functools import partial
from typing import Any, ClassVar, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..events import ByteBatch, EventBatch, EventStream
from ..nfa import NFA, MinimizeStats, QueryPartition, _query_weight, \
    compile_queries, minimize as minimize_nfa, pad_states, partition_queries
from ..xpath import Query, parse as parse_xpath
from .result import NO_MATCH, FilterResult, SparseResult


def _round_up(n: int, multiple: int) -> int:
    multiple = max(1, int(multiple))
    return max(multiple, -(-n // multiple) * multiple)


# ------------------------------------------------- sparse verdict compaction
def _compact_matches(matched, first, cols, cap: int):
    """Cumsum-compact a dense device verdict into a bounded match buffer.

    ``matched`` ``(B, K)`` bool and ``first`` ``(B, K)`` int32 live on
    device; ``cols`` ``(K,)`` int32 names each column (a query column,
    global id, or accept-lane class — ``-1`` marks dead/pad columns whose
    hits are discarded).  Every hit is assigned its rank by an exclusive
    cumsum over the flattened hit mask and scattered to that slot of a
    ``cap``-bounded buffer (out-of-range ranks drop), so the only
    device→host transfer is ``3 × cap`` int32 plus one count — delivery
    bandwidth scales with matches, not ``B × K``.  When the returned
    ``count`` exceeds ``cap`` the buffer is truncated and the caller
    must fall back to the dense path (``SparseResult.overflowed``).
    """
    hits = jnp.logical_and(matched, (cols >= 0)[None, :])
    flat = hits.reshape(-1)
    rank = jnp.cumsum(flat.astype(jnp.int32)) - 1
    dest = jnp.where(flat, rank, cap)          # non-hits park out of range
    doc = jax.lax.broadcasted_iota(jnp.int32, hits.shape, 0).reshape(-1)
    col = jnp.broadcast_to(cols[None, :], hits.shape).reshape(-1)
    buf_doc = jnp.full((cap,), -1, jnp.int32).at[dest].set(
        doc, mode="drop")
    buf_col = jnp.full((cap,), -1, jnp.int32).at[dest].set(
        col, mode="drop")
    buf_first = jnp.full((cap,), NO_MATCH, jnp.int32).at[dest].set(
        first.reshape(-1), mode="drop")
    return buf_doc, buf_col, buf_first, flat.sum(dtype=jnp.int32)


@partial(jax.jit, static_argnums=3)
def _compact_dense(matched, first, cols, cap: int):
    """Jitted :func:`_compact_matches` over a ``(B, K)`` device verdict."""
    return _compact_matches(matched, first, cols, cap)


@partial(jax.jit, static_argnums=3)
def _compact_parts(matched, first, cols, cap: int):
    """Jitted compaction over a stacked ``(P, B, Qpad)`` sharded verdict.

    ``cols`` is ``(P, Qpad)`` global ids (``-1`` = tombstoned/pad).  The
    part axis folds into the column axis, so one cumsum ranks hits
    across every part — rows come back doc-major but part-interleaved
    within a document; the host assembly lexsorts.
    """
    p, b, q = matched.shape
    m = jnp.moveaxis(matched, 0, 1).reshape(b, p * q)
    f = jnp.moveaxis(first, 0, 1).reshape(b, p * q)
    return _compact_matches(m, f, cols.reshape(-1), cap)



#: default event-axis padding bucket for the byte-ingest paths; engines
#: created with an ``event_bucket=`` option (``FilterStage`` threads its
#: own ``bucket`` through it) override this per instance
DEFAULT_EVENT_BUCKET = 128


# ----------------------------------------------------------------- the plan
class FilterPlan:
    """Frozen pytree: named device tables + static (hashable) metadata.

    ``plan.tables`` maps table name → array (the pytree leaves);
    ``plan.meta`` maps name → static value (pytree aux data, so jit
    retraces when it changes).  Instances are immutable — build a new
    plan instead of mutating one.
    """

    __slots__ = ("engine", "_names", "_arrays", "_meta")

    def __init__(self, engine: str, tables: Mapping[str, Any],
                 meta: Mapping[str, Any] | None = None) -> None:
        names = tuple(sorted(tables))
        object.__setattr__(self, "engine", engine)
        object.__setattr__(self, "_names", names)
        object.__setattr__(self, "_arrays", tuple(tables[n] for n in names))
        object.__setattr__(self, "_meta",
                           tuple(sorted((meta or {}).items())))

    def __setattr__(self, name: str, value: Any) -> None:
        raise AttributeError("FilterPlan is frozen")

    @property
    def tables(self) -> dict[str, Any]:
        return dict(zip(self._names, self._arrays))

    @property
    def meta(self) -> dict[str, Any]:
        return dict(self._meta)

    def table(self, name: str) -> Any:
        return self._arrays[self._names.index(name)]

    def __getitem__(self, name: str) -> Any:
        return self.table(name)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"FilterPlan({self.engine!r}, tables={list(self._names)}, "
                f"meta={self.meta})")

    # pytree protocol -----------------------------------------------------
    def _flatten(self):
        return self._arrays, (self.engine, self._names, self._meta)

    @classmethod
    def _unflatten(cls, aux, leaves):
        engine, names, meta = aux
        self = cls.__new__(cls)
        object.__setattr__(self, "engine", engine)
        object.__setattr__(self, "_names", names)
        object.__setattr__(self, "_arrays", tuple(leaves))
        object.__setattr__(self, "_meta", meta)
        return self


jax.tree_util.register_pytree_node(
    FilterPlan, FilterPlan._flatten, FilterPlan._unflatten)


# ------------------------------------------------------------ sharded plans
class ShardedPlan:
    """Frozen pytree of per-part :class:`FilterPlan`\\ s — the query axis
    as a scaling axis.

    The paper scales in the number of profiles by replicating query
    blocks across FPGA area and chips (§3.5/§4); here the subscription
    set is partitioned (:func:`repro.core.nfa.partition_queries`) and
    each part compiled to its own plan.  Device engines compile every
    part with **uniform state/query padding** (the engine's
    :meth:`FilterEngine.part_pads` targets), so the per-part tables
    stack into one leading-axis ``(P, ...)`` array program —
    ``jax.vmap`` on one device, ``jax.shard_map`` over the mesh
    ``"model"`` axis when one is provided.  Host engines keep raw
    per-part plans and loop them.

    Instances are immutable; subscription churn returns a **new** plan:

    * :meth:`add_queries` — appends to the least-loaded part and
      recompiles *only that part* (other parts re-pad only when the new
      part overflows a shared pad bucket), so steady-state subscribe
      cost is O(n_queries / n_parts) instead of O(n_queries);
    * :meth:`remove_queries` — pure metadata: the column is tombstoned
      in the partition index and masked out of results; the dead column
      is reclaimed the next time its part recompiles.

    Global query ids are stable across churn (see
    :class:`repro.core.nfa.QueryPartition`); results are reported over
    the *live* ids in ascending order — for a freshly planned set this
    is exactly the original query order, so sharded and unsharded
    verdicts are directly comparable.

    Pytree note: the leaves are the per-part plans' tables (so a
    ``ShardedPlan`` can cross ``jax.jit`` boundaries like any pytree);
    the partition/query bookkeeping rides in aux data and compares by
    identity — pass :meth:`stacked` (a plain :class:`FilterPlan`) into
    jitted code instead of the ``ShardedPlan`` itself.
    """

    __slots__ = ("engine", "plans", "part_cols", "part_queries",
                 "part_nfas", "pads", "n_global", "query_bucket", "shared",
                 "_engine_obj", "_stacked", "_partition")

    def __init__(self, engine_obj: "FilterEngine",
                 plans: Sequence[FilterPlan],
                 part_cols: Sequence[Sequence[int]],
                 part_queries: Sequence[Sequence[Query | None]],
                 part_nfas: Sequence[NFA],
                 pads: Mapping[str, int],
                 n_global: int,
                 query_bucket: int,
                 shared: bool) -> None:
        object.__setattr__(self, "engine", engine_obj.name)
        object.__setattr__(self, "plans", tuple(plans))
        object.__setattr__(self, "part_cols",
                           tuple(tuple(c) for c in part_cols))
        object.__setattr__(self, "part_queries",
                           tuple(tuple(q) for q in part_queries))
        object.__setattr__(self, "part_nfas", tuple(part_nfas))
        object.__setattr__(self, "pads", dict(pads))
        object.__setattr__(self, "n_global", int(n_global))
        object.__setattr__(self, "query_bucket", int(query_bucket))
        object.__setattr__(self, "shared", bool(shared))
        object.__setattr__(self, "_engine_obj", engine_obj)
        object.__setattr__(self, "_stacked", None)
        object.__setattr__(self, "_partition", None)

    def __setattr__(self, name: str, value: Any) -> None:
        raise AttributeError("ShardedPlan is frozen")

    # ----------------------------------------------------------- structure
    @property
    def n_parts(self) -> int:
        return len(self.plans)

    @property
    def n_queries(self) -> int:
        """Live (subscribed) query count."""
        return sum(1 for cols in self.part_cols for g in cols if g >= 0)

    @property
    def partition(self) -> QueryPartition:
        """Global id ↔ (part, local column) index of the current layout."""
        if self._partition is None:
            part_of = np.full(self.n_global, -1, np.int32)
            local_of = np.zeros(self.n_global, np.int32)
            for p, cols in enumerate(self.part_cols):
                for c, gid in enumerate(cols):
                    if gid >= 0:
                        part_of[gid] = p
                        local_of[gid] = c
            object.__setattr__(self, "_partition",
                               QueryPartition(part_of, local_of,
                                              self.n_parts))
        return self._partition

    def live_ids(self) -> np.ndarray:
        return self.partition.live_ids()

    def live_queries(self) -> tuple[Query, ...]:
        """Subscribed queries in global-id order — compiling these from
        scratch must reproduce this plan's verdicts exactly (the churn
        equivalence invariant)."""
        by_gid: dict[int, Query] = {}
        for cols, qs in zip(self.part_cols, self.part_queries):
            for gid, q in zip(cols, qs):
                if gid >= 0:
                    by_gid[gid] = q
        return tuple(by_gid[g] for g in sorted(by_gid))

    def index_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(part, local) gather index over live ids in global order."""
        part = self.partition
        live = part.live_ids()
        return part.part_of[live], part.local_of[live]

    def stacked(self) -> FilterPlan:
        """All parts as ONE plan with leading part axis (device engines).

        Uniform padding makes every per-part table the same shape, so
        table ``k`` stacks to ``(P, ...)`` — the array program form that
        ``vmap``/``shard_map`` partition over the mesh ``"model"`` axis.
        Cached: churn builds new ``ShardedPlan`` instances, so a cached
        stack can never go stale.
        """
        if self._stacked is None:
            names = list(self.plans[0].tables)
            tables = {k: jnp.stack([p[k] for p in self.plans])
                      for k in names}
            meta = dict(self.plans[0].meta)
            meta["n_parts"] = self.n_parts
            object.__setattr__(
                self, "_stacked", FilterPlan(self.engine, tables, meta))
        return self._stacked

    def part_sizes(self) -> np.ndarray:
        return self.partition.part_sizes()

    def gid_columns(self) -> np.ndarray:
        """``(P, Qpad)`` global id per compiled plan column.

        ``-1`` marks tombstoned and pad columns — the dead-column mask
        the sparse compaction path uses to discard their hits on device.
        """
        qpad = int(self.pads.get("n_queries", 0)) or max(
            (len(c) for c in self.part_cols), default=1)
        out = np.full((self.n_parts, qpad), -1, np.int32)
        for p, cols in enumerate(self.part_cols):
            if cols:
                out[p, :len(cols)] = cols
        return out

    # --------------------------------------------------------- rebalancing
    def part_weights(self) -> np.ndarray:
        """Estimated automaton load per part: Σ state weight of live
        queries (:func:`repro.core.nfa._query_weight` — length plus a
        loop state per ``//`` step), the same measure
        :func:`partition_queries` balances at plan time."""
        w = np.zeros(self.n_parts, np.int64)
        for p, (cols, qs) in enumerate(zip(self.part_cols,
                                           self.part_queries)):
            w[p] = sum(_query_weight(q)
                       for g, q in zip(cols, qs) if g >= 0)
        return w

    def imbalance(self) -> float:
        """Relative overload of the heaviest part: ``max/mean - 1``.

        0 means perfectly balanced; 1 means the hottest part carries
        twice the average automaton weight (and the stacked device
        program wastes half its padded area on the other parts).
        """
        w = self.part_weights().astype(float)
        mean = float(w.mean()) if w.size else 0.0
        return float(w.max() / mean - 1.0) if mean > 0 else 0.0

    def rebalance(self, *, tolerance: float = 0.25,
                  max_moves: int | None = None
                  ) -> tuple["ShardedPlan", dict]:
        """Migrate trie groups between parts until load is ~balanced.

        Long churn sequences erode the plan-time balance:
        :meth:`add_queries` always appends to the currently least-loaded
        part and :meth:`remove_queries` tombstones in place, so at 10⁵+
        subscriptions the partition drifts — one part's sub-NFA grows
        while others carry dead columns, and the uniformly-padded
        stacked program pays the hottest part's shape everywhere.

        This is the off-hot-path repair: shared-prefix trie groups (the
        :func:`partition_queries` migration unit, so prefix sharing
        survives the move) are moved greedily from the heaviest to the
        lightest part while each move strictly shrinks the spread; only
        the parts actually touched are recompiled — at the existing pad
        buckets when they fit (with an incremental restack of just those
        rows), falling back to a full re-pad otherwise.  Tombstoned
        columns of recompiled parts are compacted away for free.

        Returns ``(new_plan, stats)`` — the caller swaps the new frozen
        plan in atomically (see ``FilterStage.maybe_rebalance``); the
        old plan keeps serving until then.  Global ids, verdicts and
        live-id ordering are unchanged: rebalancing is invisible in
        results.  When the plan is already within ``tolerance``
        (``max/mean - 1 ≤ tolerance``), returns ``self`` unchanged.
        """
        from ...kernels.blocks import PadOverflow
        from ..nfa import _prefix_key

        eng = self._engine_obj
        imb0 = self.imbalance()
        stats = {"moves": 0, "moved_queries": 0, "recompiled_parts": 0,
                 "repadded": False, "imbalance_before": imb0,
                 "imbalance_after": imb0}
        if self.n_parts < 2 or imb0 <= tolerance:
            return self, stats

        # live queries per part, bucketed into trie-group migration units
        units: list[dict[Any, list[tuple[int, Query]]]] = []
        for cols, qs in zip(self.part_cols, self.part_queries):
            d: dict[Any, list[tuple[int, Query]]] = {}
            for g, q in zip(cols, qs):
                if g >= 0:
                    d.setdefault(_prefix_key(q), []).append((g, q))
            units.append(d)
        loads = [sum(_query_weight(q) for grp in d.values() for _, q in grp)
                 for d in units]
        mean = sum(loads) / len(loads)

        moves: list[tuple[int, int, int]] = []  # (donor, recv, n_queries)
        budget = max_moves if max_moves is not None else 4 * self.n_parts
        while len(moves) < budget:
            donor = int(np.argmax(loads))
            recv = int(np.argmin(loads))
            gap = loads[donor] - loads[recv]
            if gap <= 0 or loads[donor] <= (1.0 + tolerance) * mean:
                break
            # heaviest whole group that still strictly shrinks the
            # spread (w < gap ⇒ the receiver ends below the donor's old
            # load, so the same group can never ping-pong back)
            best_key, best_w = None, 0
            for key, grp in units[donor].items():
                w = sum(_query_weight(q) for _, q in grp)
                if best_w < w < gap:
                    best_key, best_w = key, w
            if best_key is not None:
                grp = units[donor].pop(best_key)
                units[recv].setdefault(best_key, []).extend(grp)
                loads[donor] -= best_w
                loads[recv] += best_w
                moves.append((donor, recv, len(grp)))
                continue
            # every group outweighs the gap (a popular prefix can dwarf
            # the per-part mean at 10⁵ profiles): split the heaviest one
            # at query granularity — co-locating a prefix group is a
            # balance heuristic, never a correctness invariant, and the
            # moved slice still shares its prefix *within* the receiver
            key = max(units[donor],
                      key=lambda k: sum(_query_weight(q)
                                        for _, q in units[donor][k]),
                      default=None)
            if key is None:
                break
            grp = units[donor][key]
            take, w = 0, 0
            for g, q in grp[:-1]:  # always leave one query behind
                qw = _query_weight(q)
                if w + qw >= gap:
                    break
                take += 1
                w += qw
                if w >= gap / 2:
                    break
            if take == 0:
                break
            units[donor][key] = grp[take:]
            units[recv].setdefault(key, []).extend(grp[:take])
            loads[donor] -= w
            loads[recv] += w
            moves.append((donor, recv, take))
        if not moves:
            return self, stats

        changed = sorted({p for d, r, _ in moves for p in (d, r)})
        part_cols = list(self.part_cols)
        part_queries = list(self.part_queries)
        part_nfas = list(self.part_nfas)
        for p in changed:
            entries = sorted(
                (g, q) for grp in units[p].values() for g, q in grp)
            part_cols[p] = tuple(g for g, _ in entries)
            part_queries[p] = tuple(q for _, q in entries)
            part_nfas[p] = eng._maybe_minimize(compile_queries(
                part_queries[p], eng.dictionary, shared=self.shared))

        fresh = eng.part_pads(part_nfas, query_bucket=self.query_bucket)
        pads, plans, stacked = self.pads, list(self.plans), self._stacked
        new_plans: dict[int, FilterPlan] | None = None
        if all(fresh.get(k, 0) <= pads.get(k, 0) for k in fresh):
            try:
                new_plans = {p: eng.plan_part(part_nfas[p], pads)
                             for p in changed}
            except PadOverflow:
                new_plans = None
        if new_plans is None:
            pads = eng.merge_pads(self.pads, fresh, part_nfas)
            plans = [eng.plan_part(nfa, pads) for nfa in part_nfas]
            stacked = None
            stats["repadded"] = True
            stats["recompiled_parts"] = self.n_parts
        else:
            for p, pl in new_plans.items():
                plans[p] = pl
            stats["recompiled_parts"] = len(changed)
            if stacked is not None:
                tables = stacked.tables
                for p in changed:
                    tables = {k: v.at[p].set(plans[p][k])
                              for k, v in tables.items()}
                stacked = FilterPlan(self.engine, tables, stacked.meta)

        sp = ShardedPlan(eng, plans, part_cols, part_queries, part_nfas,
                         pads, self.n_global, self.query_bucket,
                         self.shared)
        if stacked is not None:
            object.__setattr__(sp, "_stacked", stacked)
        stats["moves"] = len(moves)
        stats["moved_queries"] = sum(n for _, _, n in moves)
        stats["imbalance_after"] = sp.imbalance()
        return sp, stats

    def __repr__(self) -> str:  # pragma: no cover
        return (f"ShardedPlan({self.engine!r}, parts={self.n_parts}, "
                f"queries={self.n_queries}, pads={self.pads})")

    # ------------------------------------------------------ incremental churn
    def add_queries(self, queries: Sequence[Query | str]
                    ) -> tuple["ShardedPlan", list[int]]:
        """Subscribe new profiles; recompile only the least-loaded part.

        Returns ``(new_plan, new_global_ids)``.  The target part is
        compacted on the way (its tombstoned columns are dropped), and
        the other parts' plans are reused untouched unless the grown
        part overflows a shared pad bucket — only then is every part
        re-padded (a table rebuild from the stored sub-NFAs, not a
        query recompile).
        """
        from ...kernels.blocks import PadOverflow

        eng = self._engine_obj
        new_qs = [parse_xpath(q) if isinstance(q, str) else q
                  for q in queries]
        if not new_qs:
            return self, []
        sizes = self.partition.part_sizes()
        p = int(np.argmin(sizes))
        live = [(g, q) for g, q in
                zip(self.part_cols[p], self.part_queries[p]) if g >= 0]
        new_gids = list(range(self.n_global, self.n_global + len(new_qs)))
        cols_p = tuple(g for g, _ in live) + tuple(new_gids)
        qs_p = tuple(q for _, q in live) + tuple(new_qs)
        nfa_p = eng._maybe_minimize(
            compile_queries(qs_p, eng.dictionary, shared=self.shared))
        part_nfas = list(self.part_nfas)
        part_nfas[p] = nfa_p
        fresh = eng.part_pads(part_nfas, query_bucket=self.query_bucket)
        plans = list(self.plans)
        stacked = None
        one_part = None
        if all(fresh.get(k, 0) <= self.pads.get(k, 0) for k in fresh):
            # fits the existing buckets: touch one part.  Jointly-derived
            # targets (e.g. the megakernel's block layout) can still be
            # infeasible at the OLD buckets even when every key compares
            # ≤ — a PadOverflow falls through to the full replan below.
            try:
                one_part = eng.plan_part(nfa_p, self.pads)
            except PadOverflow:
                one_part = None
        if one_part is not None:
            pads = self.pads
            plans[p] = one_part
            if self._stacked is not None:
                # incremental restack: overwrite one row of the cached
                # (P, ...) tables instead of restacking all parts — the
                # device-side cost of a subscribe stays O(1/P)
                tables = {k: self._stacked[k].at[p].set(plans[p][k])
                          for k in self._stacked.tables}
                stacked = FilterPlan(self.engine, tables,
                                     self._stacked.meta)
        else:
            pads = eng.merge_pads(self.pads, fresh, part_nfas)
            plans = [eng.plan_part(nfa, pads) for nfa in part_nfas]
        part_cols = list(self.part_cols)
        part_queries = list(self.part_queries)
        part_cols[p] = cols_p
        part_queries[p] = qs_p
        sp = ShardedPlan(eng, plans, part_cols, part_queries, part_nfas,
                         pads, self.n_global + len(new_qs),
                         self.query_bucket, self.shared)
        if stacked is not None:
            object.__setattr__(sp, "_stacked", stacked)
        return sp, new_gids

    def remove_queries(self, gids: Sequence[int]) -> "ShardedPlan":
        """Unsubscribe by global id — O(1) metadata, no recompilation.

        The columns stay in the compiled plans (tombstoned: excluded
        from the partition index and from every result) and are
        physically dropped the next time their part recompiles.
        """
        dead = set(int(g) for g in gids)
        part = self.partition
        for g in dead:
            if not (0 <= g < self.n_global) or part.part_of[g] < 0:
                raise KeyError(f"query id {g} is not subscribed")
        part_cols = [tuple(-1 if g in dead else g for g in cols)
                     for cols in self.part_cols]
        sp = ShardedPlan(self._engine_obj, self.plans, part_cols,
                         self.part_queries, self.part_nfas, self.pads,
                         self.n_global, self.query_bucket, self.shared)
        # plans are identical (tombstoning lives in the index), so the
        # stacked tables carry over — a removal never restacks
        object.__setattr__(sp, "_stacked", self._stacked)
        return sp

    # pytree protocol -----------------------------------------------------
    def _flatten(self):
        aux = (self._engine_obj, self.part_cols, self.part_queries,
               self.part_nfas, tuple(sorted(self.pads.items())),
               self.n_global, self.query_bucket, self.shared)
        return self.plans, aux

    @classmethod
    def _unflatten(cls, aux, plans):
        engine_obj, cols, qs, nfas, pads, n_global, bucket, shared = aux
        return cls(engine_obj, tuple(plans), cols, qs, nfas, dict(pads),
                   n_global, bucket, shared)


jax.tree_util.register_pytree_node(
    ShardedPlan, ShardedPlan._flatten, ShardedPlan._unflatten)


# --------------------------------------------------------------- the engine
class FilterEngine(abc.ABC):
    """Uniform engine interface: compile once, filter batches forever.

    ``__init__`` compiles the profile set (via :meth:`plan`) exactly once;
    :meth:`filter_batch` is then a pure function of the plan and an
    :class:`~repro.core.events.EventBatch` — the only document format an
    engine ever sees.
    """

    #: registry key, set by the :func:`register` decorator
    name: ClassVar[str] = ""

    #: state-axis pad multiple this engine's plan tables require (32-state
    #: packed words, 128-lane MXU tiles, 1 = no padding).  Overridable per
    #: instance via the ``state_multiple=`` engine option and recorded in
    #: plan metadata — :func:`repro.core.nfa.pad_states` is always called
    #: with this value, never a hard-coded constant.
    state_multiple: ClassVar[int] = 1

    #: True when the engine runs per-part plans as ONE stacked device
    #: program (vmap/shard_map over the leading part axis); False (host
    #: engines) loops parts in python.
    device_sharded: ClassVar[bool] = False

    #: uniform pad targets threaded by :meth:`plan_part` for the duration
    #: of the :meth:`plan` call (sharded plans need every per-part table —
    #: including kernel block tables — at identical shapes so they stack)
    _plan_pads: Mapping[str, int] | None = None

    def __init__(self, nfa: NFA, dictionary=None, **options: Any) -> None:
        self.dictionary = dictionary
        if "state_multiple" in options:
            self.state_multiple = int(options.pop("state_multiple"))
        # global NFA minimization (``minimize=True`` engine option):
        # merge behavior-identical states across queries on top of the
        # shared-prefix trie before compiling any plan — the sharded and
        # churn paths route through _maybe_minimize so every compiled
        # part shrinks the same way
        self._minimize = bool(options.pop("minimize", False))
        self.minimize_stats: MinimizeStats | None = None
        if self._minimize:
            nfa, self.minimize_stats = minimize_nfa(nfa)
        # persistent compiled-plan cache (``plan_cache=`` engine option:
        # a PlanCache instance or a directory path) — every compilation
        # site routes through _plan_cached, so cold starts and shadow
        # rebuilds skip recompilation on a content-hash hit
        cache = options.pop("plan_cache", None)
        if isinstance(cache, (str, os.PathLike)):
            from ...checkpoint.store import PlanCache
            cache = PlanCache(os.fspath(cache))
        self.plan_cache = cache
        self.nfa = nfa
        self.options = options
        self.n_queries = nfa.n_queries
        self.plan_: FilterPlan = self._plan_cached(nfa)

    def _maybe_minimize(self, nfa: NFA) -> NFA:
        """Apply global minimization when the engine was built with it.

        Every compilation site — the initial plan, per-part sharded
        plans, churn recompiles, rebalance recompiles — routes new NFAs
        through here so verdict-equivalence is preserved uniformly.
        """
        if not getattr(self, "_minimize", False):
            return nfa
        return minimize_nfa(nfa)[0]

    # ------------------------------------------------------------ contract
    @abc.abstractmethod
    def plan(self, nfa: NFA) -> FilterPlan:
        """Compile the NFA into this engine's device tables (once)."""

    @abc.abstractmethod
    def filter_batch(self, batch: EventBatch) -> FilterResult:
        """Filter a document batch; returns a ``(B, Q)`` result."""

    # ------------------------------------------------- explicit-plan filter
    def _prep(self, batch: EventBatch) -> tuple:
        """Plan-independent document-side preparation (device engines).

        Whatever the engine's compiled program consumes — event arrays,
        level buckets, chunk layouts.  Shared across every part of a
        sharded plan: the document structure does not depend on which
        queries are asked of it.
        """
        raise NotImplementedError(
            f"{self.name}: no device prep (host engine)")

    def _run_with_plan(self, plan: FilterPlan, prep: tuple):
        """Pure-jax body: explicit plan + prepped batch → (matched, first).

        Must be vmappable over the plan's tables — the sharded path maps
        it over the leading part axis of :meth:`ShardedPlan.stacked`.
        """
        raise NotImplementedError(
            f"{self.name}: no device run (host engine)")

    def filter_batch_with_plan(self, plan: FilterPlan,
                               batch: EventBatch) -> FilterResult:
        """:meth:`filter_batch` against an explicit plan (any compiled
        profile set, not just ``self.plan_``) — the primitive both the
        unsharded and the per-part sharded paths are built from."""
        matched, first = self._run_with_plan(plan, self._prep(batch))
        return FilterResult(np.asarray(matched), np.asarray(first))

    # ------------------------------------------------------- sharded plans
    def part_pads(self, parts: Sequence[NFA], *,
                  query_bucket: int = 8) -> dict[str, int]:
        """Uniform pad targets for a set of partition NFAs.

        Device engines pad every part to common bucket sizes so the
        per-part tables stack (state axis to the engine's
        ``state_multiple``, query axis to ``query_bucket``); subclass
        engines extend with their own table axes (e.g. matscan's
        ``kmax``, levelwise's tag space).  Host engines return ``{}``
        (parts are looped, shapes never need to agree).  Buckets give
        churn headroom: an added query only forces a global re-pad when
        its part overflows a bucket boundary.
        """
        if not self.device_sharded:
            return {}
        s = max((nfa.n_states for nfa in parts), default=1)
        q = max((nfa.n_queries for nfa in parts), default=1)
        return {"n_states": _round_up(s, self.state_multiple),
                "n_queries": _round_up(max(q, 1), query_bucket)}

    def plan_part(self, nfa: NFA, pads: Mapping[str, int]) -> FilterPlan:
        """Compile one partition's NFA at the shared pad targets.

        Routes through the persistent plan cache when one is configured
        (see :meth:`_plan_cached`); the actual compile is
        :meth:`_plan_part_uncached`.
        """
        return self._plan_cached(nfa, pads)

    def _plan_part_uncached(self, nfa: NFA,
                            pads: Mapping[str, int]) -> FilterPlan:
        """The compile body of :meth:`plan_part`.

        The pad dict is exposed to :meth:`plan` as ``self._plan_pads``
        for the duration of the call — engines with derived plan tables
        whose shapes are not a pure function of ``(n_states, n_queries)``
        (e.g. the streaming megakernel's block count and accept-lane
        width) read their uniform targets from it so per-part tables
        stack along the leading part axis.
        """
        if not pads:
            return self.plan(nfa)
        if "n_tags" in pads and pads["n_tags"] > nfa.n_tags:
            nfa = dataclasses.replace(nfa, n_tags=pads["n_tags"])
        nfa = pad_states(nfa, to=pads["n_states"])
        self._plan_pads = pads
        try:
            plan = self.plan(nfa)
        finally:
            self._plan_pads = None
        return self._pad_plan_queries(plan, pads["n_queries"])

    # ------------------------------------------------ persistent plan cache
    def plan_cache_key(self, nfa: NFA,
                       pads: Mapping[str, int] | None = None) -> str:
        """Content hash identifying one compiled plan: NFA tables × pad
        targets × kernel config.

        Everything the compiled tables are a deterministic function of
        goes into the hash — the dense NFA table contents (so two
        different profile sets can only collide if they compile
        identically anyway), the query/tag space sizes, the engine name
        and its remaining options (block sizes, autotune policy, sparse
        knobs …), the state multiple, the uniform pad targets, and the
        kernel-environment switches (interpret mode, VMEM/SMEM budgets)
        that steer :meth:`kernel_config`.  A stale cache hit is
        therefore structurally impossible: any input that could change
        the tables changes the key.
        """
        from ...kernels import interpret_default

        h = hashlib.sha256()
        for part in (
                "v1", self.name, str(self.state_multiple),
                repr(sorted((k, repr(v)) for k, v in self.options.items())),
                str(int(nfa.n_tags)), str(int(nfa.n_queries)),
                "shared" if nfa.shared else "unshared",
                repr(sorted((pads or {}).items())),
                str(bool(interpret_default())),
                os.environ.get("REPRO_PALLAS_VMEM_BUDGET", ""),
                os.environ.get("REPRO_PALLAS_SMEM_BUDGET", "")):
            h.update(part.encode())
            h.update(b"\x00")
        for arr in nfa.tables:
            a = np.asarray(arr)
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        return h.hexdigest()[:40]

    def _plan_cached(self, nfa: NFA,
                     pads: Mapping[str, int] | None = None) -> FilterPlan:
        """Compile via the persistent plan cache when one is configured.

        Only device engines cache (host plans hold python objects, and
        there is no compile cost to skip); a hit rebuilds the
        :class:`FilterPlan` from the stored numpy tables + JSON metadata
        with no ``plan()`` call at all — the cold-start/crash-recovery
        fast path.  A miss compiles and persists through the
        crash-safe :meth:`repro.checkpoint.store.PlanCache.put`.
        """
        cache = self.plan_cache
        if cache is None or not self.device_sharded:
            return (self._plan_part_uncached(nfa, pads)
                    if pads is not None else self.plan(nfa))
        key = self.plan_cache_key(nfa, pads)
        hit = cache.get(key)
        if hit is not None:
            tables, manifest = hit
            return FilterPlan(manifest.get("engine", self.name),
                              {k: jnp.asarray(v) for k, v in tables.items()},
                              manifest.get("meta", {}))
        plan = (self._plan_part_uncached(nfa, pads)
                if pads is not None else self.plan(nfa))
        # metadata must survive a JSON round-trip bit-exactly (it is jit
        # aux data); a plan whose meta does not is simply not cached
        meta = dict(plan.meta)
        if json.loads(json.dumps(meta)) == meta:
            cache.put(key, {k: np.asarray(v)
                            for k, v in plan.tables.items()},
                      {"engine": plan.engine, "meta": meta})
        return plan

    def _pad_plan_queries(self, plan: FilterPlan,
                          n_queries: int) -> FilterPlan:
        """Pad the plan's query axis with never-matching columns.

        Default handles engines whose only per-query table is
        ``accept_state``: padding columns accept at state 0 (the root,
        which no OPEN event ever activates), so they report unmatched
        forever — inert by construction, like pad states.
        """
        acc = plan["accept_state"]
        extra = n_queries - int(acc.shape[0])
        if extra <= 0:
            return plan
        tables = plan.tables
        # pad on the host: a device concatenate would XLA-compile once
        # per novel shape, dominating per-op churn latency
        acc_h = np.asarray(acc)
        tables["accept_state"] = jnp.asarray(
            np.concatenate([acc_h, np.zeros(extra, acc_h.dtype)]))
        return FilterPlan(plan.engine, tables, plan.meta)

    def merge_pads(self, old: Mapping[str, int], new: Mapping[str, int],
                   parts: Sequence[NFA]) -> dict[str, int]:
        """Reconcile churn pad targets when new queries overflow a bucket.

        The default is the per-key maximum of the existing and freshly
        derived targets.  Engines whose derived table shapes are *joint*
        functions of several targets (the streaming megakernel's block
        count and accept-lane width both depend on the block size)
        override this to re-derive the dependent keys at the merged
        independent ones — a per-key max of separately-derived values
        can otherwise be infeasible.
        """
        return {k: max(new.get(k, 0), old.get(k, 0))
                for k in set(new) | set(old)}

    # ---------------------------------------------- kernel autotune hook
    def kernel_config(self, n_states: int, n_tags: int) -> dict | None:
        """Plan-level kernel selection + launch-shape autotune hook.

        Engines with a Pallas hot path override this to pick their
        kernel launch parameters (state-block size, SMEM chunk length,
        …) from the plan's *static* shape at ``plan()`` time — so the
        choice is compiled into the plan once, not re-derived per batch.
        :meth:`autotune_blocks` is the shared sizing helper; the
        streaming engine adopts it for the megakernel, and any engine
        that grows a kernel path can reuse the same hook + helper pair.
        ``None`` (the default) means the engine has no kernel path.
        """
        return None

    @staticmethod
    def autotune_blocks(n_states: int, max_depth: int, *, n_tags: int,
                        vmem_budget: int | None = None,
                        smem_budget: int | None = None,
                        chunk: int = 256) -> dict:
        """Pick a (``blk``, ``chunk``) launch shape from static bounds.

        ``blk`` (states per kernel block, a multiple of 32) is the
        largest power-of-two candidate whose per-program VMEM footprint
        — packed-word stack, per-tag word masks, parent gather lanes —
        fits ``vmem_budget``, clamped down to the padded state count (no
        point in blocks wider than the whole NFA).  ``chunk`` (events
        per SMEM DMA chunk) is clamped to half of ``smem_budget`` (the
        event buffer is double-buffered int32).  Engine options override
        both knobs; this is only the default policy.

        Budgets default from the ``REPRO_PALLAS_VMEM_BUDGET`` /
        ``REPRO_PALLAS_SMEM_BUDGET`` env vars (bytes) when the caller
        passes ``None`` — CI and the measured autotune search exercise
        small-budget layouts without monkeypatching; explicit arguments
        always win.
        """
        if vmem_budget is None:
            vmem_budget = int(os.environ.get(
                "REPRO_PALLAS_VMEM_BUDGET", 4 << 20))
        if smem_budget is None:
            smem_budget = int(os.environ.get(
                "REPRO_PALLAS_SMEM_BUDGET", 8 << 10))
        blk = 32
        for cand in (1024, 512, 256, 128, 64, 32):
            wb = cand // 32
            need = 4 * ((max_depth + 2) * wb    # packed-word VMEM stack
                        + (n_tags + 1) * wb     # per-tag word masks
                        + 2 * 32 * wb           # parent word/bit lanes
                        + 4 * wb)               # state/work rows
            if need <= vmem_budget:
                blk = cand
                break
        blk = min(blk, _round_up(max(n_states, 1), 32))
        chunk = max(32, min(int(chunk), smem_budget // (2 * 4)))
        return {"blk": blk, "chunk": chunk}

    def plan_sharded(self, n_parts: int, *,
                     query_bucket: int = 8) -> ShardedPlan:
        """Partition this engine's profile set and compile per-part plans.

        The counterpart of :meth:`plan` for the sharded contract: split
        the subscription set (:func:`repro.core.nfa.partition_queries`),
        compile each part at uniform pad targets, and return the frozen
        :class:`ShardedPlan` that :meth:`filter_batch_sharded` executes
        and whose ``add_queries``/``remove_queries`` absorb churn.
        """
        parts, partition = partition_queries(
            list(self.nfa.queries), n_parts, self.dictionary,
            shared=self.nfa.shared)
        parts = [self._maybe_minimize(p) for p in parts]
        # local ids are assigned in ascending gid order within each part,
        # so appending in gid order reproduces the column layout
        part_cols: list[list[int]] = [[] for _ in range(n_parts)]
        for gid in range(len(self.nfa.queries)):
            part_cols[int(partition.part_of[gid])].append(gid)
        part_queries = [[self.nfa.queries[g] for g in cols]
                        for cols in part_cols]
        pads = self.part_pads(parts, query_bucket=query_bucket)
        plans = [self.plan_part(nfa, pads) for nfa in parts]
        return ShardedPlan(self, plans, part_cols, part_queries, parts,
                           pads, len(self.nfa.queries), query_bucket,
                           self.nfa.shared)

    def filter_batch_sharded(self, batch: EventBatch, sharded: ShardedPlan,
                             *, mesh=None) -> FilterResult:
        """Filter through a partitioned plan; ``(B, Q_live)`` result.

        Device engines run every part in ONE compiled program: the
        stacked ``(P, ...)`` tables are vmapped over the part axis, and
        when ``mesh`` is given (see
        :func:`repro.launch.mesh.make_filter_mesh`) the part axis is
        partitioned over the mesh ``"model"`` axis with ``shard_map`` —
        each device advances only its slice of the subscription set,
        the paper's profiles-across-chips scaling.  Host engines loop
        parts.  Columns come back in live-global-id order (original
        query order for an unchurned plan), tombstones excluded.
        """
        part_of, local_of = sharded.index_arrays()
        if self.device_sharded:
            matched, first = self._run_sharded(batch, sharded, mesh)
            matched = np.asarray(matched)   # (P, B, Qpad)
            first = np.asarray(first)
            return FilterResult(matched[part_of, :, local_of].T,
                                first[part_of, :, local_of].T)
        outs = [self.filter_batch_with_plan(plan, batch)
                for plan in sharded.plans]
        b = batch.batch_size
        matched = np.zeros((b, part_of.shape[0]), bool)
        first = np.full((b, part_of.shape[0]), NO_MATCH, np.int32)
        for j, (p, c) in enumerate(zip(part_of, local_of)):
            matched[:, j] = outs[p].matched[:, c]
            first[:, j] = outs[p].first_event[:, c]
        return FilterResult(matched, first)

    # ------------------------------------------------- sparse verdict path
    def match_cap(self, batch_size: int, n_cols: int,
                  cap: int | None = None) -> int:
        """Resolve the bounded match-buffer size for one sparse call.

        Explicit argument wins, then the ``match_cap=`` engine option,
        then ``match_cap`` from the compiled plan's metadata (set via
        :meth:`kernel_config` so autotune/persisted configs can carry
        it); the default budgets 32 matches per document (floor 4096) —
        far above realistic selectivity at 10⁵ profiles, while the dense
        fallback keeps rare hot batches exact.  Clamped to the dense
        size, past which overflow is impossible anyway.
        """
        if cap is None:
            cap = self.options.get("match_cap")
        if cap is None:
            plan = getattr(self, "plan_", None)
            if plan is not None:
                cap = plan.meta.get("match_cap")
        if cap is None:
            cap = max(4096, 32 * batch_size)
        return int(max(1, min(int(cap), batch_size * max(1, n_cols))))

    def _sparse_from_buffers(self, bufs, count: int, cap: int, *,
                             batch_size: int, n_queries: int,
                             live_ids=None, sort: bool = False,
                             meta: dict | None = None,
                             dense_fallback=None) -> SparseResult:
        """Assemble a :class:`SparseResult` from device compaction output.

        ``bufs`` is the ``(doc, col, first)`` buffer triple from
        :func:`_compact_matches`; only the first ``count`` rows are
        real.  ``count > cap`` means the buffer overflowed — the
        verdicts are recomputed via ``dense_fallback()`` (exact, just
        without the bandwidth win), flagged ``overflowed`` and named
        ``path="dense-overflow"`` (the route that WOULD have run stays
        visible as ``attempted_path``).
        """
        meta = dict(meta or (), match_cap=cap)
        if count > cap:
            sp = dense_fallback().sparsify(live_ids)
            sp.overflowed = True
            sp.meta.update(meta, matches=count,
                           attempted_path=meta.get("path"),
                           path="dense-overflow")
            return sp
        docs, cols, first = (np.asarray(b)[:count] for b in bufs)
        if sort:  # part-interleaved producers: restore (doc, id) order
            order = np.lexsort((cols, docs))
            docs, cols, first = docs[order], cols[order], first[order]
        return SparseResult(
            docs, cols, first, batch_size=batch_size, n_queries=n_queries,
            live_ids=(None if live_ids is None
                      else np.asarray(live_ids, np.int32)),
            meta=meta)

    def filter_batch_sparse(self, batch: EventBatch, *,
                            match_cap: int | None = None) -> SparseResult:
        """Sparse-verdict twin of :meth:`filter_batch`.

        Device engines compact the verdict **on device** (see
        :func:`_compact_matches`): the host receives a bounded
        ``(doc_id, query_id, first_event)`` match list instead of the
        dense ``(B, Q)`` bitmap, so result bandwidth scales with the
        matches.  Host engines sparsify the dense result (wire format
        only — they never had a device transfer to save).
        :meth:`SparseResult.densify` round-trips bit-exactly.
        """
        if not self.device_sharded:
            sp = self.filter_batch(batch).sparsify()
            sp.meta["path"] = "dense-host"
            return sp
        matched, first = self._run_with_plan(self.plan_, self._prep(batch))
        b = batch.batch_size
        q = int(matched.shape[-1])
        cap = self.match_cap(b, q, match_cap)
        *bufs, n = _compact_dense(matched, first,
                                  jnp.arange(q, dtype=jnp.int32), cap)
        return self._sparse_from_buffers(
            bufs, int(n), cap, batch_size=b, n_queries=q,
            meta={"path": "device-compact"},
            dense_fallback=lambda: FilterResult(np.asarray(matched),
                                                np.asarray(first)))

    def filter_batch_sharded_sparse(self, batch: EventBatch,
                                    sharded: ShardedPlan, *, mesh=None,
                                    match_cap: int | None = None
                                    ) -> SparseResult:
        """Sparse-verdict twin of :meth:`filter_batch_sharded`.

        One device compaction over the stacked ``(P, B, Qpad)`` output
        with columns named by **global subscriber id** (tombstoned and
        pad columns discarded on device), so at 10⁵ profiles the
        device→host transfer is the match list, not ``B × Q_live``.
        ``query_ids`` are global ids; ``densify`` restores the dense
        live-column layout of :meth:`filter_batch_sharded` bit-exactly.
        """
        live_ids = sharded.live_ids()
        if not self.device_sharded:
            sp = self.filter_batch_sharded(
                batch, sharded, mesh=mesh).sparsify(live_ids)
            sp.meta["path"] = "dense-host"
            return sp
        matched, first = self._run_sharded(batch, sharded, mesh)
        b = batch.batch_size
        cap = self.match_cap(b, len(live_ids), match_cap)
        *bufs, n = _compact_parts(matched, first,
                                  jnp.asarray(sharded.gid_columns()), cap)

        def dense_fallback() -> FilterResult:
            part_of, local_of = sharded.index_arrays()
            return FilterResult(
                np.asarray(matched)[part_of, :, local_of].T,
                np.asarray(first)[part_of, :, local_of].T)

        return self._sparse_from_buffers(
            bufs, int(n), cap, batch_size=b, n_queries=len(live_ids),
            live_ids=live_ids, sort=True,
            meta={"path": "device-compact"}, dense_fallback=dense_fallback)

    def filter_batch_sharded2d_sparse(self, batch: EventBatch,
                                      sharded: ShardedPlan, *, mesh,
                                      match_cap: int | None = None
                                      ) -> SparseResult:
        """Sparse wire format over the 2-D (data × model) path.

        The 2-D program's outputs are already partitioned per device;
        this sparsifies the gathered result on the host — the match-list
        format for delivery, without an extra device pass.
        """
        sp = self.filter_batch_sharded2d(
            batch, sharded, mesh=mesh).sparsify(sharded.live_ids())
        sp.meta["path"] = "dense-2d"
        return sp

    def filter_bytes_sparse(self, bb: ByteBatch, *,
                            bucket: int | None = None,
                            match_cap: int | None = None) -> SparseResult:
        """Bytes in, sparse match list out (device parse + compaction)."""
        from ...kernels.parse import DEFAULT_MAX_DEPTH, parse_batch

        max_depth = int(getattr(self, "max_depth", DEFAULT_MAX_DEPTH))
        return self.filter_batch_sparse(
            parse_batch(bb, n_events=bb.event_bound(
                bucket=self._event_bucket(bucket)), max_depth=max_depth),
            match_cap=match_cap)

    def filter_bytes_sharded_sparse(self, bb: ByteBatch,
                                    sharded: ShardedPlan, *,
                                    bucket: int | None = None, mesh=None,
                                    match_cap: int | None = None
                                    ) -> SparseResult:
        """Sharded bytes→sparse-verdict twin."""
        from ...kernels.parse import DEFAULT_MAX_DEPTH, parse_batch

        max_depth = int(getattr(self, "max_depth", DEFAULT_MAX_DEPTH))
        return self.filter_batch_sharded_sparse(
            parse_batch(bb, n_events=bb.event_bound(
                bucket=self._event_bucket(bucket)), max_depth=max_depth),
            sharded, mesh=mesh, match_cap=match_cap)

    def _cached_exec(self, key, build):
        """Per-engine cache of compiled sharded callables, keyed on the
        execution form (1d/2d/bytes2d × mesh × static shape knobs); jit
        keys on the plan pytree structure and prep shapes on top, so
        pad-bucket growth or a new batch shape retraces exactly once."""
        cache = getattr(self, "_sharded_exec", None)
        if cache is None:
            cache = {}
            self._sharded_exec = cache
        fn = cache.get(key)
        if fn is None:
            fn = build()
            cache[key] = fn
        return fn

    def _check_model_axis(self, sharded: ShardedPlan, mesh) -> None:
        if mesh is None:
            return
        axis = dict(mesh.shape).get("model", 1)
        if axis > 1 and sharded.n_parts % axis != 0:
            raise ValueError(
                f"n_parts={sharded.n_parts} not divisible by mesh "
                f"model axis {axis}")

    def _vmapped_parts(self):
        def vmapped(plan, *prep_args):
            return jax.vmap(
                lambda pl: self._run_with_plan(pl, prep_args))(plan)
        return vmapped

    def _run_sharded(self, batch: EventBatch, sharded: ShardedPlan, mesh):
        """Stacked-parts execution: vmap, or shard_map over the mesh."""
        prep = self._prep(batch)
        stacked = sharded.stacked()
        self._check_model_axis(sharded, mesh)

        def build():
            vmapped = self._vmapped_parts()
            if mesh is not None:
                ps = jax.sharding.PartitionSpec
                return jax.jit(jax.shard_map(
                    vmapped, mesh=mesh,
                    in_specs=(ps("model"),) + (ps(),) * len(prep),
                    out_specs=(ps("model"), ps("model")),
                    check_vma=False))
            return jax.jit(vmapped)

        return self._cached_exec(("1d", mesh), build)(stacked, *prep)

    def _event_bucket(self, bucket: int | None) -> int:
        """Resolve an event-axis padding bucket for the byte paths.

        ``None`` (the default everywhere a caller did not choose one)
        falls back to the engine's ``event_bucket=`` option — which
        ``FilterStage`` sets to its own ``bucket`` — so every ingest
        path of one stage pads to the same boundaries instead of a
        hard-coded 128 silently taking over on some of them.
        """
        if bucket is not None:
            return int(bucket)
        return int(self.options.get("event_bucket", DEFAULT_EVENT_BUCKET))

    def filter_bytes_sharded(self, bb: ByteBatch, sharded: ShardedPlan, *,
                             bucket: int | None = None,
                             mesh=None) -> FilterResult:
        """Sharded twin of :meth:`filter_bytes`: device parse once, then
        one stacked parts program — bytes in, ``(B, Q_live)`` out."""
        from ...kernels.parse import DEFAULT_MAX_DEPTH, parse_batch

        max_depth = int(getattr(self, "max_depth", DEFAULT_MAX_DEPTH))
        return self.filter_batch_sharded(
            parse_batch(bb,
                        n_events=bb.event_bound(
                            bucket=self._event_bucket(bucket)),
                        max_depth=max_depth),
            sharded, mesh=mesh)

    # ------------------------------------------------ 2-D (data × model)
    def _prep_arrays(self, kind, tag, depth, parent, valid, n_events):
        """Device-side document prep straight from parse outputs.

        Implemented by engines whose plan metadata records ``prep ==
        "events-device"`` (streaming, matscan: their compiled program
        consumes the raw event stream) — what lets the fused
        bytes→verdict shard_map program run parse *and* filter inside
        one per-device body.  Engines with host-side prep (the levelwise
        family buckets by depth in numpy) or host execution never get
        here.
        """
        raise NotImplementedError(
            f"{self.name}: no device parse prep "
            f"(plan meta 'prep' is not 'events-device')")

    def _mesh_axes2d(self, mesh) -> tuple[int, int]:
        if mesh is None:
            raise ValueError(
                "the 2-D path needs a ('data', 'model') mesh — see "
                "repro.launch.mesh.make_filter_mesh(data_shards=...)")
        shape = dict(mesh.shape)
        if "data" not in shape or "model" not in shape:
            raise ValueError(
                f"2-D filtering needs a ('data', 'model') mesh, got axes "
                f"{tuple(shape)}")
        return shape["data"], shape["model"]

    def _gather2d(self, matched, first, sharded: ShardedPlan, b0: int):
        """Zero-arg materializer over the raw (P, Bpad, Qpad) outputs.

        Calling it blocks on the async device computation, gathers live
        columns in global-id order and slices off batch-pad rows — the
        deferred half of :meth:`dispatch_batch_sharded2d`.
        """
        part_of, local_of = sharded.index_arrays()

        def materialize() -> FilterResult:
            m = np.asarray(matched)[part_of, :, local_of].T[:b0]
            f = np.asarray(first)[part_of, :, local_of].T[:b0]
            return FilterResult(m, f)

        return materialize

    def dispatch_batch_sharded2d(self, batch: EventBatch,
                                 sharded: ShardedPlan, *, mesh):
        """Launch the 2-D (data × model) program; returns a zero-arg
        materializer — call it to block and get the ``(B, Q_live)``
        :class:`FilterResult`.

        Both of the paper's replication axes (§3.5) in ONE ``shard_map``
        program: the stacked per-part plan tables are partitioned over
        the mesh ``"model"`` axis (each device advances 1/P of the
        subscription set) and the document batch over ``"data"`` (each
        replica row sees 1/D of the stream).  The batch axis is padded
        to a multiple of the data axis with inert all-PAD documents
        (sliced back off the result), so any batch size is servable.

        Dispatch is asynchronous — the returned callable is the
        synchronization point, which is what the double-buffered ingest
        loop overlaps the next batch's ``device_put`` against.  Host
        engines compute eagerly (the part loop is the bit-equivalence
        oracle for this path) and return an already-resolved thunk.
        """
        if not self.device_sharded:
            res = self.filter_batch_sharded(batch, sharded)
            return lambda: res
        data_ax, _ = self._mesh_axes2d(mesh)
        self._check_model_axis(sharded, mesh)
        b0 = batch.batch_size
        batch = batch.pad_batch_to(_round_up(b0, data_ax))
        prep = self._prep(batch)
        stacked = sharded.stacked()

        def build():
            ps = jax.sharding.PartitionSpec
            return jax.jit(jax.shard_map(
                self._vmapped_parts(), mesh=mesh,
                in_specs=(ps("model"),) + (ps("data"),) * len(prep),
                out_specs=(ps("model", "data"), ps("model", "data")),
                check_vma=False))

        matched, first = self._cached_exec(("2d", mesh), build)(
            stacked, *prep)
        return self._gather2d(matched, first, sharded, b0)

    def filter_batch_sharded2d(self, batch: EventBatch,
                               sharded: ShardedPlan, *,
                               mesh) -> FilterResult:
        """Blocking convenience over :meth:`dispatch_batch_sharded2d`."""
        return self.dispatch_batch_sharded2d(batch, sharded, mesh=mesh)()

    def dispatch_bytes_sharded2d(self, bb: ByteBatch, sharded: ShardedPlan,
                                 *, bucket: int | None = None, mesh,
                                 n_events: int | None = None):
        """ByteBatch twin of :meth:`dispatch_batch_sharded2d`.

        When the plan's document prep is device-resident (plan metadata
        ``prep == "events-device"``), this is ONE shard_map bytes→verdict
        program: each device parses its ``"data"`` slice of the wire
        bytes locally (the parse kernels inline into the body) and runs
        its ``"model"`` slice of the stacked plan — the paper's same-chip
        parser+filter, replicated in both dimensions, with no host hop
        between payload and verdict.  Engines with host-side prep parse
        on device then run the 2-D event program; host engines loop
        parts (the bit-equivalence oracle).

        ``n_events`` is the static compacted event bound; pass a
        precomputed one when ``bb`` is device-resident (the pipelined
        ingest loop computes it from the host copy before ``device_put``
        — computing it here would force a device→host read of the byte
        tensor).  The fused path trusts the engine's ``max_depth`` bound
        (a pure-device program cannot host-check depth); the parse-first
        path keeps ``parse_batch``'s raise-on-overflow check.
        """
        from ...kernels.parse import (DEFAULT_MAX_DEPTH, parse_arrays,
                                      parse_batch)

        max_depth = int(getattr(self, "max_depth", DEFAULT_MAX_DEPTH))
        if n_events is None:
            n_events = bb.event_bound(bucket=self._event_bucket(bucket))
        if not self.device_sharded:
            # part-loop oracle; the explicit n_events keeps a placed
            # byte tensor from being read back just to re-derive it
            res = self.filter_batch_sharded(
                parse_batch(bb, n_events=n_events, max_depth=max_depth),
                sharded)
            return lambda: res
        if sharded.plans[0].meta.get("prep") != "events-device":
            eb = parse_batch(bb, n_events=n_events, max_depth=max_depth)
            return self.dispatch_batch_sharded2d(eb, sharded, mesh=mesh)
        data_ax, _ = self._mesh_axes2d(mesh)
        self._check_model_axis(sharded, mesh)
        b0 = bb.batch_size
        bb = bb.pad_batch_to(_round_up(b0, data_ax))
        stacked = sharded.stacked()

        def build():
            vmapped = self._vmapped_parts()

            def body(plan, data):
                parsed = parse_arrays(data, n_events=n_events,
                                      max_depth=max_depth)
                return vmapped(plan, *self._prep_arrays(*parsed))

            ps = jax.sharding.PartitionSpec
            return jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(ps("model"), ps("data")),
                out_specs=(ps("model", "data"), ps("model", "data")),
                check_vma=False))

        matched, first = self._cached_exec(
            ("bytes2d", mesh, n_events, max_depth), build)(
                stacked, jnp.asarray(bb.data))
        return self._gather2d(matched, first, sharded, b0)

    def filter_bytes_sharded2d(self, bb: ByteBatch, sharded: ShardedPlan,
                               *, bucket: int | None = None, mesh,
                               n_events: int | None = None) -> FilterResult:
        """Blocking convenience over :meth:`dispatch_bytes_sharded2d`."""
        return self.dispatch_bytes_sharded2d(
            bb, sharded, bucket=bucket, mesh=mesh, n_events=n_events)()

    # ------------------------------------------------------ byte ingestion
    def filter_bytes(self, bb: ByteBatch, *,
                     bucket: int | None = None) -> FilterResult:
        """Raw wire bytes → ``(B, Q)`` verdict, parsed on device.

        The ingestion seam of the paper's same-chip architecture: the
        batch is parsed by :func:`repro.kernels.parse.parse_batch` (no
        per-event host Python) and fed to :meth:`filter_batch` as a
        device-resident :class:`~repro.core.events.EventBatch`.  Device
        engines that can fuse parse+filter into one compiled program
        override this (see ``StreamingEngine.filter_bytes``).

        The parse honours the engine's own ``max_depth`` bound when it
        has one and *raises* on documents nested deeper (parse_batch's
        depth check) — never a silently clipped verdict.  ``bucket``
        bounds the compiled event-axis shapes; ``None`` resolves through
        :meth:`_event_bucket` (callers with their own bucketing policy —
        e.g. ``FilterStage`` — thread theirs via the ``event_bucket=``
        engine option or pass it explicitly).
        """
        from ...kernels.parse import DEFAULT_MAX_DEPTH, parse_batch

        max_depth = int(getattr(self, "max_depth", DEFAULT_MAX_DEPTH))
        return self.filter_batch(
            parse_batch(bb,
                        n_events=bb.event_bound(
                            bucket=self._event_bucket(bucket)),
                        max_depth=max_depth))

    # --------------------------------------------------------- conveniences
    def filter_document(self, ev: EventStream) -> FilterResult:
        """Single-document convenience on top of :meth:`filter_batch`."""
        return self.filter_batch(EventBatch.from_streams([ev]))[0]

    def filter_documents(self, docs) -> FilterResult:
        return self.filter_batch(EventBatch.from_streams(list(docs)))


# -------------------------------------------------------------- the registry
_REGISTRY: dict[str, type[FilterEngine]] = {}


def register(name: str):
    """Class decorator: make the engine constructible by string key."""

    def deco(cls: type[FilterEngine]) -> type[FilterEngine]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get(name: str) -> type[FilterEngine]:
    """Engine class for ``name`` (raises with the known names on miss)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def create(name: str, nfa: NFA, dictionary=None,
           **options: Any) -> FilterEngine:
    """Construct a registered engine: ``create('levelwise', nfa)``."""
    return get(name)(nfa, dictionary=dictionary, **options)


def names() -> tuple[str, ...]:
    """All registered engine keys, sorted."""
    return tuple(sorted(_REGISTRY))
