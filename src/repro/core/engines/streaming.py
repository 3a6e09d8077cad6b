"""Paper-faithful JAX streaming engine.

Direct datapath analogue of the FPGA design (Fig 4/5): every NFA state is
one "hardware" lane; each event advances *all* lanes simultaneously; a
bounded on-chip stack of packed 32-bit state bitmasks realizes the paper's
tag stack (push on open, pop on close); the TOS-match is the read of the
stack top that feeds the transition.

Two executions of the same semantics:

* **megakernel** (``kernel="pallas"``, the default device path on TPU) —
  :func:`repro.kernels.stream_filter.stream_filter_pallas`: one fused
  Pallas program gridded over (documents × state-word blocks), state
  packed in VMEM end to end, events walked from SMEM chunks by the
  scalar core.  Block tables are compiled into the plan
  (:func:`repro.kernels.blocks.state_layout`), block/chunk sizes come
  from the plan-level autotune hook
  (:meth:`repro.core.engines.base.FilterEngine.autotune_blocks`).
* **scan** (``kernel="scan"``, the oracle/fallback and the default off
  TPU, where Pallas only interprets) — one ``lax.scan`` step per event;
  the kernel is bit-identical to it by construction and by test
  (tests/test_megakernel.py).

State bitmasks are packed ``uint32`` words (the FPGA keeps one FF per
state; we keep one bit), so the per-document stack is ``(max_depth+2,
S/32)`` words — small enough for VMEM at thousands of queries.  The one
``max_depth`` in the plan metadata bounds *both* paths, so kernel and
scan can never disagree on stack clipping.

Compilation happens once, in :meth:`StreamingEngine.plan`; the batched
path is ``vmap`` of the scan — or one megakernel launch — over an
:class:`~repro.core.events.EventBatch`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import blocks as blocks_mod
from ...kernels import interpret_default
from ...kernels import stream_filter as sf
from ...kernels.parse import DEFAULT_MAX_DEPTH
from ..dictionary import OPEN_NBYTES
from ..events import (CLOSE, OPEN, SEG_SENTINEL, ByteBatch, EventBatch,
                      EventStream, SegmentPack, pack_segments)
from ..nfa import NFA, WILD_TAG, pad_states
from ..spans import span
from . import base
from .result import NO_MATCH, FilterResult, SparseResult

#: execution modes for the ``kernel=`` engine option
KERNEL_MODES = ("auto", "pallas", "scan")

#: bytes per DMA chunk of the one-launch bytes megakernel (distinct from
#: the event kernel's events-per-chunk ``chunk``) and the segment-packer
#: capacity target — both autotunable (:mod:`repro.kernels.autotune`)
#: and overridable via the ``byte_chunk=`` / ``segment_target=`` engine
#: options
DEFAULT_BYTE_CHUNK = 512
DEFAULT_SEGMENT_TARGET = 4096

#: VMEM budget for the fused-epilogue match buffer (three lane-dense
#: int32 fields, :func:`repro.kernels.stream_filter.epilogue_vmem_bytes`).
#: Past this the bounded buffer would crowd the block tables out of
#: VMEM, so ``sparse_epilogue="auto"`` falls back to the two-launch lane
#: compaction for that cap
DEFAULT_EPILOGUE_VMEM = 4 * 1024 * 1024

#: launch-shape knobs a measured-autotune cache entry may override
TUNABLE_KEYS = ("blk", "chunk", "byte_chunk", "grid_order",
                "segment_target")


def _pack_words(bits: jax.Array) -> jax.Array:
    """(..., S) int32 0/1 → (..., S/32) uint32."""
    s = bits.shape[-1]
    lanes = bits.reshape(bits.shape[:-1] + (s // 32, 32)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (lanes * weights).sum(axis=-1, dtype=jnp.uint32)


def _unpack_words(words: jax.Array) -> jax.Array:
    """(..., W) uint32 → (..., W*32) int32 0/1."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_states", "max_depth"))
def _run(kind, tag, in_state, in_tag, selfloop, init_words, accept_state,
         *, n_states: int, max_depth: int):
    n_ev = kind.shape[0]
    n_q = accept_state.shape[0]
    n_w = n_states // 32
    stack0 = jnp.zeros((max_depth + 2, n_w), dtype=jnp.uint32)
    stack0 = stack0.at[0].set(init_words)

    def step(carry, xs):
        stack, depth, matched, first = carry
        k, t, i = xs
        is_open = k == OPEN
        is_close = k == CLOSE
        row = jax.lax.dynamic_index_in_dim(stack, depth, keepdims=False)
        bits = _unpack_words(row)                       # (S,) int32 — the FFs
        tagmatch = ((in_tag == t) | (in_tag == WILD_TAG)).astype(jnp.int32)
        src = jnp.take(bits, in_state, axis=0)          # previous-block wire
        nxt = (src & tagmatch) | (selfloop & bits)      # all lanes, one "clock"
        words = _pack_words(nxt)
        # push on open (write at depth+1), no-op otherwise
        widx = jnp.clip(depth + 1, 0, max_depth + 1)
        old = jax.lax.dynamic_index_in_dim(stack, widx, keepdims=False)
        new_row = jnp.where(is_open, words, old)
        stack = jax.lax.dynamic_update_index_in_dim(stack, new_row, widx, 0)
        depth = depth + jnp.where(is_open, 1, jnp.where(is_close, -1, 0))
        depth = jnp.clip(depth, 0, max_depth + 1)
        # accept lanes → priority-encoder analogue
        acc = jnp.take(nxt, accept_state, axis=0).astype(bool) & is_open
        newly = acc & (~matched)
        first = jnp.where(newly, i, first)
        matched = matched | acc
        return (stack, depth, matched, first), None

    carry0 = (stack0, jnp.int32(0),
              jnp.zeros(n_q, dtype=bool), jnp.full(n_q, NO_MATCH, jnp.int32))
    (stack, depth, matched, first), _ = jax.lax.scan(
        step, carry0, (kind, tag, jnp.arange(n_ev, dtype=jnp.int32)))
    return matched, first


@jax.jit
def _run_batch(plan: base.FilterPlan, kind: jax.Array, tag: jax.Array):
    """Scan path: vmap of the event scan over a (B, N) batch; plan is a
    pytree arg, so one trace serves every batch of the same shape."""
    meta = plan.meta
    fn = functools.partial(
        _run,
        in_state=plan["in_state"], in_tag=plan["in_tag"],
        selfloop=plan["selfloop"], init_words=plan["init_words"],
        accept_state=plan["accept_state"],
        n_states=meta["n_states"], max_depth=meta["max_depth"])
    return jax.vmap(fn, in_axes=(0, 0))(kind, tag)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_batch_kernel(plan: base.FilterPlan, kind: jax.Array,
                      tag: jax.Array, interpret: bool | None = None):
    """Megakernel path: one fused Pallas launch over (docs × blocks),
    then the accept-lane → query gather (the priority encoder)."""
    meta = plan.meta
    mb, fb = sf.stream_filter_pallas(
        sf.fuse_events(kind, tag),
        plan["kb_tagmask"], plan["kb_pw"], plan["kb_pb"],
        plan["kb_selfloop"], plan["kb_init"],
        plan["kb_acc_word"], plan["kb_acc_bit"],
        max_depth=meta["max_depth"], chunk=meta["chunk"],
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))
    matched = mb[:, plan["kb_acc_block"], plan["kb_acc_slot"]] != 0
    first = fb[:, plan["kb_acc_block"], plan["kb_acc_slot"]]
    return matched, first


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_parts_kernel(plan: base.FilterPlan, kind: jax.Array,
                      tag: jax.Array, interpret: bool | None = None):
    """Stacked sharded plan (leading part axis) through ONE megakernel
    launch: parts fold into the block-grid axis — more profiles are just
    more independent blocks, the paper's profiles-across-chips scaling
    without a second program.  Returns (P, B, Qpad) matched/first."""
    meta = plan.meta
    g = meta["n_blocks"]

    def fold(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    mb, fb = sf.stream_filter_pallas(
        sf.fuse_events(kind, tag),
        fold(plan["kb_tagmask"]), fold(plan["kb_pw"]), fold(plan["kb_pb"]),
        fold(plan["kb_selfloop"]), fold(plan["kb_init"]),
        fold(plan["kb_acc_word"]), fold(plan["kb_acc_bit"]),
        max_depth=meta["max_depth"], chunk=meta["chunk"],
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))
    b = kind.shape[0]
    p = plan["kb_selfloop"].shape[0]
    mb = mb.reshape(b, p, g, -1)
    fb = fb.reshape(b, p, g, -1)
    gather = jax.vmap(lambda m, ab, sl: m[:, ab, sl], in_axes=(1, 0, 0))
    matched = gather(mb, plan["kb_acc_block"], plan["kb_acc_slot"]) != 0
    first = gather(fb, plan["kb_acc_block"], plan["kb_acc_slot"])
    return matched, first


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _run_batch_kernel_sparse(plan: base.FilterPlan, kind: jax.Array,
                             tag: jax.Array, lane_cls: jax.Array, cap: int,
                             interpret: bool | None = None):
    """Megakernel → bounded match buffer, skipping the dense gather.

    The compaction runs on the raw ``(B, G, QB)`` accept-lane bitmap —
    the kernel's native output — with each lane named by its **accept
    class** (``lane_cls``, ``-1`` = inert lane).  Minimized plans map
    many subscribers onto one lane, so the device emits one row per
    (document, accept class): strictly fewer rows than subscribers
    matched.  The host expands classes back to subscriber ids.
    """
    meta = plan.meta
    mb, fb = sf.stream_filter_pallas(
        sf.fuse_events(kind, tag),
        plan["kb_tagmask"], plan["kb_pw"], plan["kb_pb"],
        plan["kb_selfloop"], plan["kb_init"],
        plan["kb_acc_word"], plan["kb_acc_bit"],
        max_depth=meta["max_depth"], chunk=meta["chunk"],
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))
    b = mb.shape[0]
    return base._compact_matches(
        mb.reshape(b, -1) != 0, fb.reshape(b, -1), lane_cls, cap)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _run_parts_kernel_sparse(plan: base.FilterPlan, kind: jax.Array,
                             tag: jax.Array, lane_cls: jax.Array, cap: int,
                             interpret: bool | None = None):
    """Sharded twin of :func:`_run_batch_kernel_sparse`: the part axis
    folds into the block grid (ONE launch) and ``lane_cls`` carries
    globally-offset class ids in the same folded ``(P·G·QB,)`` order, so
    one cumsum compacts every part's accept lanes together."""
    meta = plan.meta

    def fold(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    mb, fb = sf.stream_filter_pallas(
        sf.fuse_events(kind, tag),
        fold(plan["kb_tagmask"]), fold(plan["kb_pw"]), fold(plan["kb_pb"]),
        fold(plan["kb_selfloop"]), fold(plan["kb_init"]),
        fold(plan["kb_acc_word"]), fold(plan["kb_acc_bit"]),
        max_depth=meta["max_depth"], chunk=meta["chunk"],
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))
    b = mb.shape[0]
    return base._compact_matches(
        mb.reshape(b, -1) != 0, fb.reshape(b, -1), lane_cls, cap)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _run_batch_kernel_fused(plan: base.FilterPlan, kind: jax.Array,
                            tag: jax.Array, doc_ids: jax.Array,
                            lane_cls: jax.Array, cap: int,
                            interpret: bool | None = None):
    """In-kernel sparse epilogue: the megakernel emits the bounded
    ``(doc, class, first)`` match buffer itself — the ``(B, G, QB)``
    accept bitmap never exists outside VMEM (the program's only outputs
    are the buffer and the running counter)."""
    meta = plan.meta
    return sf.stream_filter_pallas_sparse(
        sf.fuse_events(kind, tag), doc_ids,
        plan["kb_tagmask"], plan["kb_pw"], plan["kb_pb"],
        plan["kb_selfloop"], plan["kb_init"],
        plan["kb_acc_word"], plan["kb_acc_bit"], lane_cls,
        cap=cap, max_depth=meta["max_depth"], chunk=meta["chunk"],
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _run_parts_kernel_fused(plan: base.FilterPlan, kind: jax.Array,
                            tag: jax.Array, doc_ids: jax.Array,
                            lane_cls: jax.Array, cap: int,
                            interpret: bool | None = None):
    """Sharded twin of :func:`_run_batch_kernel_fused`: parts fold into
    the block grid (ONE launch) and ``lane_cls`` (P, G, QB) carries
    globally-offset class ids, so the kernel's running counter compacts
    every part's accept lanes into one buffer."""
    meta = plan.meta

    def fold(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    return sf.stream_filter_pallas_sparse(
        sf.fuse_events(kind, tag), doc_ids,
        fold(plan["kb_tagmask"]), fold(plan["kb_pw"]), fold(plan["kb_pb"]),
        fold(plan["kb_selfloop"]), fold(plan["kb_init"]),
        fold(plan["kb_acc_word"]), fold(plan["kb_acc_bit"]),
        lane_cls.reshape(-1, lane_cls.shape[-1]),
        cap=cap, max_depth=meta["max_depth"], chunk=meta["chunk"],
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _run_bytes_fused_sparse(plan: base.FilterPlan, data: jax.Array,
                            starts: jax.Array, doc_map: jax.Array,
                            lane_cls: jax.Array, cap: int,
                            interpret: bool | None = None):
    """ONE launch raw bytes → bounded match list: the fused bytes
    datapath ending in the in-kernel sparse epilogue (no event tensor,
    no accept bitmap, anywhere in the program)."""
    meta = plan.meta
    return sf.stream_filter_bytes_pallas_sparse(
        data, starts, doc_map,
        plan["kb_tagmask"], plan["kb_pw"], plan["kb_pb"],
        plan["kb_selfloop"], plan["kb_init"],
        plan["kb_acc_word"], plan["kb_acc_bit"], lane_cls,
        cap=cap, max_depth=meta["max_depth"],
        chunk=meta.get("byte_chunk", DEFAULT_BYTE_CHUNK),
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _run_parts_bytes_fused_sparse(plan: base.FilterPlan, data: jax.Array,
                                  starts: jax.Array, doc_map: jax.Array,
                                  lane_cls: jax.Array, cap: int,
                                  interpret: bool | None = None):
    """Stacked sharded plan through ONE bytes→match-list launch."""
    meta = plan.meta

    def fold(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    return sf.stream_filter_bytes_pallas_sparse(
        data, starts, doc_map,
        fold(plan["kb_tagmask"]), fold(plan["kb_pw"]), fold(plan["kb_pb"]),
        fold(plan["kb_selfloop"]), fold(plan["kb_init"]),
        fold(plan["kb_acc_word"]), fold(plan["kb_acc_bit"]),
        lane_cls.reshape(-1, lane_cls.shape[-1]),
        cap=cap, max_depth=meta["max_depth"],
        chunk=meta.get("byte_chunk", DEFAULT_BYTE_CHUNK),
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))


def _device_rows(buf, cnt, cap: int, ndev: int = 1
                 ) -> tuple[tuple, int, bool]:
    """Stacked per-device ``(cap, 3)`` match buffers + counts → host rows.

    ``shard_map`` concatenates each device's bounded buffer along the
    leading axis; only the first ``min(count_d, cap)`` rows of each are
    real.  Returns ``((docs, cls, first), total_count, overflowed)``
    where overflow means ANY device saturated its buffer.  A count row
    may carry more columns (:func:`_walk_counts`); the first is the
    match count.
    """
    buf = np.asarray(buf).reshape(ndev, -1, 3)
    cnt = np.asarray(cnt).reshape(ndev, -1)[:, 0]
    rows = np.concatenate(
        [buf[dv, :min(int(c), cap)] for dv, c in enumerate(cnt)])
    return ((rows[:, 0], rows[:, 1], rows[:, 2]),
            int(cnt.sum()), bool((cnt > int(cap)).any()))


def _walk_counts(cnt) -> dict:
    """What the bytes kernels' walks counted, summed over devices: the
    tag starts (second column of the count rows :func:`_device_rows`
    has read back) and the chunks fetched from HBM (third column); a
    host copy already made, so no further sync."""
    cols = np.asarray(cnt).reshape(-1, 3).sum(axis=0)
    return {"tag_starts": int(cols[1]), "stream_chunks": int(cols[2])}


def _lane_classes(plan: base.FilterPlan) -> tuple[np.ndarray, np.ndarray]:
    """Accept-class tables of one kernel plan (host-side, on demand).

    Returns ``(class_of, lane_cls)``: ``class_of[q]`` is the accept
    class of query column q (``-1`` for inert pad columns) and
    ``lane_cls[g, qb]`` names each kernel lane's class (``-1`` for
    lanes no query accepts on, including every block's reserved inert
    lane).  Classes are numbered by first query occurrence, so member
    lists come out in ascending column order.  Derived from the
    many-to-one ``kb_acc_block``/``kb_acc_slot`` mapping rather than
    stored in the plan: the tables are pure bookkeeping the jitted
    program never reads.
    """
    ab = np.asarray(plan["kb_acc_block"])
    sl = np.asarray(plan["kb_acc_slot"])
    g, qb = np.asarray(plan["kb_acc_word"]).shape[-2:]
    inert = sl >= qb - 1          # the reserved inert lane
    key = ab.astype(np.int64) * qb + sl
    kv = key[~inert]
    uniq, inv = np.unique(kv, return_inverse=True)
    first_idx = np.full(uniq.shape, kv.shape[0], np.int64)
    np.minimum.at(first_idx, inv, np.arange(kv.shape[0]))
    rank = np.empty(uniq.shape, np.int64)
    rank[np.argsort(first_idx, kind="stable")] = np.arange(uniq.shape[0])
    class_of = np.full(key.shape, -1, np.int32)
    class_of[~inert] = rank[inv]
    lane_cls = np.full((g, qb), -1, np.int32)
    lane_cls[uniq // qb, uniq % qb] = rank
    return class_of, lane_cls


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_bytes_fused(plan: base.FilterPlan, data: jax.Array,
                     starts: jax.Array, interpret: bool | None = None):
    """ONE-launch bytes→verdict: the whole predecode+compact+filter
    datapath as a single Pallas program (no EventBatch through HBM) —
    see :func:`repro.kernels.stream_filter.stream_filter_bytes_pallas`.
    ``data``/``starts`` are segment form (an unpacked batch is the
    degenerate one-doc-per-segment case); returns (S, D, Q) matched
    bool / first int32 in segment-slot order."""
    meta = plan.meta
    mb, fb = sf.stream_filter_bytes_pallas(
        data, starts,
        plan["kb_tagmask"], plan["kb_pw"], plan["kb_pb"],
        plan["kb_selfloop"], plan["kb_init"],
        plan["kb_acc_word"], plan["kb_acc_bit"],
        max_depth=meta["max_depth"],
        chunk=meta.get("byte_chunk", DEFAULT_BYTE_CHUNK),
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))
    mb = jnp.transpose(mb, (0, 2, 1, 3))    # (S, D, G, QB)
    fb = jnp.transpose(fb, (0, 2, 1, 3))
    matched = mb[:, :, plan["kb_acc_block"], plan["kb_acc_slot"]] != 0
    first = fb[:, :, plan["kb_acc_block"], plan["kb_acc_slot"]]
    return matched, first


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_parts_bytes_fused(plan: base.FilterPlan, data: jax.Array,
                           starts: jax.Array,
                           interpret: bool | None = None):
    """Stacked sharded plan through ONE bytes→verdict launch: the part
    axis folds into the block grid exactly like :func:`_run_parts_kernel`.
    Returns (P, S, D, Qpad) matched/first in segment-slot order."""
    meta = plan.meta
    g = meta["n_blocks"]

    def fold(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    mb, fb = sf.stream_filter_bytes_pallas(
        data, starts,
        fold(plan["kb_tagmask"]), fold(plan["kb_pw"]), fold(plan["kb_pb"]),
        fold(plan["kb_selfloop"]), fold(plan["kb_init"]),
        fold(plan["kb_acc_word"]), fold(plan["kb_acc_bit"]),
        max_depth=meta["max_depth"],
        chunk=meta.get("byte_chunk", DEFAULT_BYTE_CHUNK),
        interpret=interpret, grid_order=meta.get("grid_order", "bg"))
    s = data.shape[0]
    p = plan["kb_selfloop"].shape[0]
    d = starts.shape[1] - 1
    mb = mb.reshape(s, p, g, d, -1).transpose(1, 0, 3, 2, 4)  # (P,S,D,G,QB)
    fb = fb.reshape(s, p, g, d, -1).transpose(1, 0, 3, 2, 4)
    gather = jax.vmap(lambda m, ab, sl: m[:, :, ab, sl], in_axes=(0, 0, 0))
    matched = gather(mb, plan["kb_acc_block"], plan["kb_acc_slot"]) != 0
    first = gather(fb, plan["kb_acc_block"], plan["kb_acc_slot"])
    return matched, first


@functools.partial(jax.jit, static_argnames=("n_events", "kernel",
                                             "interpret"))
def _run_bytes_batch(plan: base.FilterPlan, data: jax.Array,
                     n_events: int | None = None, kernel: bool = False,
                     interpret: bool | None = None):
    """Fused ingest+filter: (B, L) raw wire bytes → (B, Q) verdicts as ONE
    compiled program — the paper's same-chip parser+filter (§1).

    The one byte→event pipeline (:func:`repro.kernels.parse.parse_arrays`:
    batched pre-decode + cumsum compaction) and the event-stream state
    advance — the megakernel when ``kernel=True``, the scan otherwise —
    inline into a single XLA computation; the structure outputs this
    engine doesn't read (depth/parent scans) are dead-code-eliminated.
    Between the byte tensor going in and the verdict coming out there is
    no host transfer and no per-event Python.  ``n_events`` is the static
    compacted length (callers pass the tight ``ByteBatch.event_bound``;
    defaults to the worst case L/4).
    """
    from repro.kernels import parse as parse_mod

    if n_events is None:
        n_events = max(1, data.shape[1] // OPEN_NBYTES)
    kind, tag, _depth, _parent, _valid, _n = parse_mod.parse_arrays(
        data, n_events=n_events)
    if kernel:
        return _run_batch_kernel(plan, kind.astype(jnp.int32), tag,
                                 interpret=interpret)
    return _run_batch(plan, kind.astype(jnp.int32), tag)


@base.register("streaming")
class StreamingEngine(base.FilterEngine):
    """Public API: compile once (``plan``), filter many documents.

    Engine options:

    * ``kernel=`` — ``"auto"`` (default: the megakernel on a real TPU,
      the scan elsewhere — the Pallas interpreter is a correctness tool,
      not a fast path), ``"pallas"`` (force the megakernel), ``"scan"``
      (force the oracle scan).
    * ``blk=`` / ``chunk=`` — override the autotuned states-per-block /
      events-per-SMEM-chunk launch shape (see
      :meth:`~repro.core.engines.base.FilterEngine.autotune_blocks`).
    * ``kernel_interpret=`` — force the Pallas interpret flag (tests);
      ``None`` auto-detects from the backend.
    * ``event_bucket=`` — event-axis padding bucket for the byte paths.
    * ``fuse=`` — ``True`` (default): byte ingestion runs the ONE-launch
      bytes→verdict megakernel; ``False``: the two-stage
      parse-then-filter program (the comparison baseline).
    * ``pack=`` / ``segment_target=`` — segment-pack ragged byte batches
      (host first-fit-decreasing packer, see
      :func:`repro.core.events.pack_segments`) before the fused kernel.
    * ``byte_chunk=`` / ``grid_order=`` — bytes-per-DMA-chunk and grid
      iteration order of the fused kernel.
    * ``sparse_epilogue=`` — ``"auto"`` (default: in-kernel bounded
      match-list emission whenever the ``(match_cap, 3)`` buffer fits
      the epilogue VMEM budget), ``"on"`` / ``"off"`` to force it.
    * ``match_cap=`` — bounded match-buffer size for sparse calls (also
      threaded via plan meta).
    * ``vmem_budget=`` / ``smem_budget=`` — static autotune budgets
      (else the ``REPRO_PALLAS_*_BUDGET`` env vars, else defaults).
    * ``autotune="measured"`` — overlay the persisted measured-search
      best config (:mod:`repro.kernels.autotune`) for this plan shape.
    """

    #: packed-word layout: the state axis must tile into 32-bit words
    state_multiple = 32
    device_sharded = True

    def __init__(self, nfa: NFA, dictionary=None,
                 max_depth: int = DEFAULT_MAX_DEPTH, **options) -> None:
        self.max_depth = max_depth
        sm = int(options.get("state_multiple", self.state_multiple))
        if sm % 32 != 0:
            raise ValueError(
                f"streaming packs 32-state words; state_multiple={sm} "
                f"is not a multiple of 32")
        mode = options.get("kernel", "auto")
        if mode not in KERNEL_MODES:
            raise ValueError(
                f"kernel={mode!r} is not one of {KERNEL_MODES}")
        self.kernel_mode = mode
        # resolved ONCE, before plan() runs: plans carry the kb_* block
        # tables only when this engine will actually run the megakernel
        # (scan-only engines skip the layout work and the table memory)
        self.kernel_enabled = (mode == "pallas"
                               or (mode == "auto"
                                   and not interpret_default()))
        super().__init__(nfa, dictionary, **options)

    # ------------------------------------------------------ kernel routing
    def _kernel_on(self) -> bool:
        """Megakernel or scan?  ``auto`` picks the kernel exactly when
        Pallas compiles for this backend (a real TPU); the choice is
        frozen at engine construction, matching the plan's tables."""
        return self.kernel_enabled

    def _kernel_interpret(self) -> bool | None:
        ki = self.options.get("kernel_interpret")
        return None if ki is None else bool(ki)

    def kernel_config(self, n_states: int, n_tags: int) -> dict:
        """Megakernel launch shape: static policy → measured cache →
        explicit engine options, in increasing precedence.

        The static :meth:`autotune_blocks` formula (honouring the
        ``vmem_budget=`` / ``smem_budget=`` options and their env vars)
        seeds the config; with ``autotune="measured"`` a persisted
        best-config from :mod:`repro.kernels.autotune` overlays it for
        this plan shape; explicit ``blk=`` / ``chunk=`` /
        ``byte_chunk=`` / ``grid_order=`` / ``segment_target=`` options
        always win.
        """
        vb = self.options.get("vmem_budget")
        sb = self.options.get("smem_budget")
        cfg = self.autotune_blocks(
            n_states, self.max_depth, n_tags=n_tags,
            vmem_budget=None if vb is None else int(vb),
            smem_budget=None if sb is None else int(sb))
        cfg.setdefault("byte_chunk", DEFAULT_BYTE_CHUNK)
        cfg.setdefault("grid_order", "bg")
        cfg.setdefault("segment_target", DEFAULT_SEGMENT_TARGET)
        if self.options.get("autotune") == "measured":
            from ...kernels import autotune as autotune_mod

            ki = self._kernel_interpret()
            backend = ("interpret"
                       if (ki if ki is not None else interpret_default())
                       else "compiled")
            hit = autotune_mod.cached_config(autotune_mod.plan_key(
                backend, n_states, n_tags, self.max_depth,
                self.state_multiple))
            if hit:
                cfg.update({k: hit[k] for k in TUNABLE_KEYS if k in hit})
        for k in TUNABLE_KEYS:
            if k in self.options:
                cfg[k] = self.options[k]
        cfg["blk"] = int(cfg["blk"])
        cfg["chunk"] = max(32, int(cfg["chunk"]))
        cfg["byte_chunk"] = max(32, int(cfg["byte_chunk"]))
        cfg["segment_target"] = max(1, int(cfg["segment_target"]))
        if cfg["grid_order"] not in sf.GRID_ORDERS:
            raise ValueError(
                f"grid_order={cfg['grid_order']!r} is not one of "
                f"{sf.GRID_ORDERS}")
        return cfg

    def plan(self, nfa: NFA) -> base.FilterPlan:
        nfa = pad_states(nfa, self.state_multiple)
        t = nfa.tables
        init_words = jax.device_get(
            _pack_words(jnp.asarray(t.init.astype(np.int32))))
        tables = dict(
            in_state=jnp.asarray(t.in_state),
            in_tag=jnp.asarray(t.in_tag),
            selfloop=jnp.asarray(t.selfloop.astype(np.int32)),
            init_words=jnp.asarray(init_words),
            accept_state=jnp.asarray(t.accept_state),
        )
        meta = {"n_states": int(t.in_state.shape[0]),
                # ONE stack bound for scan and kernel alike — threaded
                # from here everywhere, never a per-path default
                "max_depth": self.max_depth,
                "state_multiple": self.state_multiple,
                # document prep is pure-device (scan and kernel both
                # consume the raw event stream), so the 2-D mesh path
                # can fuse parse+filter into one shard_map program
                "prep": "events-device"}
        if self.kernel_enabled:
            pads = dict(self._plan_pads or {})
            cfg = self.kernel_config(nfa.n_states, nfa.n_tags)
            mk = blocks_mod.state_layout(
                nfa, blk=int(pads.get("blk", cfg["blk"])),
                n_blocks=pads.get("n_blocks"),
                block_queries=pads.get("block_queries"))
            # megakernel block tables (kb_*): bit-packed per-block form
            # of the same NFA, compiled once per plan
            tables.update(
                kb_tagmask=jnp.asarray(mk.tagmask),
                kb_pw=jnp.asarray(mk.pw),
                kb_pb=jnp.asarray(mk.pb),
                kb_selfloop=jnp.asarray(mk.selfloop_words),
                kb_init=jnp.asarray(mk.init_words),
                kb_acc_word=jnp.asarray(mk.acc_word),
                kb_acc_bit=jnp.asarray(mk.acc_bit),
                kb_acc_block=jnp.asarray(mk.acc_block),
                kb_acc_slot=jnp.asarray(mk.acc_slot),
            )
            meta.update(blk=mk.blk, chunk=cfg["chunk"],
                        n_blocks=mk.n_blocks,
                        block_queries=mk.block_queries,
                        byte_chunk=cfg["byte_chunk"],
                        grid_order=cfg["grid_order"],
                        segment_target=cfg["segment_target"])
            if "match_cap" in self.options:
                meta["match_cap"] = int(self.options["match_cap"])
        return base.FilterPlan("streaming", tables, meta)

    # ------------------------------------------------------- sharded hooks
    def _kernel_pad_targets(self, parts, pads, *, min_blk: int = 0) -> dict:
        """Uniform megakernel layout targets for ``parts`` at the given
        (``n_states``, ``n_tags``) pads: one common block size (the
        autotuned candidate grown to every part's largest subtree and to
        ``min_blk``), then the block count and accept-lane width each
        part needs AT that block size — jointly derived, so the returned
        set is always feasible for these parts."""
        cfg = self.kernel_config(pads["n_states"], pads["n_tags"])
        padded = [pad_states(nfa, to=pads["n_states"]) for nfa in parts]
        blk = max([int(cfg["blk"]), int(min_blk)]
                  + [blocks_mod.min_block_size(nfa) for nfa in padded])
        layouts = [blocks_mod.state_layout(nfa, blk=blk) for nfa in padded]
        return {"blk": max([blk] + [lo.blk for lo in layouts]),
                "n_blocks": base._round_up(
                    max(lo.n_blocks for lo in layouts), 2),
                "block_queries": base._round_up(
                    max(lo.block_queries for lo in layouts), 8)}

    def part_pads(self, parts, *, query_bucket: int = 8):
        """Uniform pad targets incl. the megakernel block axes.

        Per-part block tables stack along the leading part axis, so all
        parts must agree on the tag space, the block size, the block
        count and the accept-lane width; each target is bucketed so
        churn rarely forces an all-parts replan.  Scan-only engines skip
        the kernel targets entirely (their plans carry no block tables).
        """
        pads = super().part_pads(parts, query_bucket=query_bucket)
        if not pads:
            return pads
        pads["n_tags"] = base._round_up(
            max((nfa.n_tags for nfa in parts), default=1), 64)
        if self.kernel_enabled:
            pads.update(self._kernel_pad_targets(parts, pads))
        return pads

    def merge_pads(self, old, new, parts):
        """Churn reconcile: per-key max for the independent targets,
        then re-derive the block layout keys at the merged block size —
        a per-key max of (``blk``, ``n_blocks``, ``block_queries``)
        derived at *different* block sizes can be infeasible (bigger
        blocks pack more subtrees, needing more accept lanes per
        block)."""
        merged = super().merge_pads(old, new, parts)
        if not self.kernel_enabled or "blk" not in merged:
            return merged
        # re-derive AT the final merged block size: layouts computed at
        # a smaller blk can under-count the lanes/blocks a bigger block
        # needs, so min_blk pins the derivation to the merged value
        targets = self._kernel_pad_targets(
            parts, {"n_states": merged["n_states"],
                    "n_tags": merged["n_tags"]},
            min_blk=merged["blk"])
        # keep monotone growth vs the old buckets (stacking headroom),
        # but never below what the merged block size actually needs
        for k, v in targets.items():
            merged[k] = max(merged.get(k, 0), v)
        return merged

    def _pad_plan_queries(self, plan: base.FilterPlan,
                          n_queries: int) -> base.FilterPlan:
        """Pad the query axis: accept columns at state 0 (never matches)
        and megakernel accept lanes at every block's reserved inert lane
        (``QB-1``, wired to the local root) — inert by construction."""
        if not self.kernel_enabled:  # scan plans carry no kb_* tables
            return super()._pad_plan_queries(plan, n_queries)
        acc = np.asarray(plan["accept_state"])
        extra = n_queries - int(acc.shape[0])
        if extra <= 0:
            return plan
        qb = plan.meta["block_queries"]
        tables = plan.tables
        ab = np.asarray(plan["kb_acc_block"])
        sl = np.asarray(plan["kb_acc_slot"])
        # pad on the host: a device concatenate would XLA-compile once
        # per novel shape, dominating per-op churn latency
        tables["accept_state"] = jnp.asarray(
            np.concatenate([acc, np.zeros(extra, acc.dtype)]))
        tables["kb_acc_block"] = jnp.asarray(
            np.concatenate([ab, np.zeros(extra, ab.dtype)]))
        tables["kb_acc_slot"] = jnp.asarray(
            np.concatenate([sl, np.full(extra, qb - 1, sl.dtype)]))
        return base.FilterPlan(plan.engine, tables, plan.meta)

    def _vmapped_parts(self):
        """Kernel path: parts fold into the megakernel's block grid (one
        launch, no vmap-of-pallas); scan path: the base vmap."""
        if not self._kernel_on():
            return super()._vmapped_parts()
        interpret = self._kernel_interpret()

        def run_parts(plan, *prep):
            kind, tag = prep
            return _run_parts_kernel(plan, kind, tag, interpret=interpret)

        return run_parts

    # --------------------------------------------------- explicit-plan body
    def _prep(self, batch: EventBatch) -> tuple:
        return (jnp.asarray(batch.kind.astype(np.int32)),
                jnp.asarray(batch.tag_id))

    def _prep_arrays(self, kind, tag, depth, parent, valid, n_events):
        # the state advance reads only (kind, tag); depth/parent/valid
        # are dead-code-eliminated out of the fused program
        return (kind.astype(jnp.int32), tag)

    def _run_with_plan(self, plan: base.FilterPlan, prep: tuple):
        kind, tag = prep
        if self._kernel_on():
            return _run_batch_kernel(plan, kind, tag,
                                     interpret=self._kernel_interpret())
        return _run_batch(plan, kind, tag)

    def filter_document(self, ev: EventStream) -> FilterResult:
        p = self.plan_
        matched, first = _run(
            jnp.asarray(ev.kind.astype(np.int32)),
            jnp.asarray(ev.tag_id),
            p["in_state"], p["in_tag"], p["selfloop"], p["init_words"],
            p["accept_state"],
            n_states=p.meta["n_states"], max_depth=p.meta["max_depth"])
        return FilterResult(np.asarray(matched), np.asarray(first))

    def filter_batch(self, batch: EventBatch) -> FilterResult:
        return self.filter_batch_with_plan(self.plan_, batch)

    # --------------------------------------------- lane-space sparse path
    def _lane_memo(self, obj, build):
        """Tiny identity-keyed memo for per-plan lane-class tables (plans
        are frozen, so identity is validity; bounded so churned-away
        plans don't pin memory)."""
        cache = self.__dict__.setdefault("_lane_cache", {})
        hit = cache.get(id(obj))
        if hit is not None and hit[0] is obj:
            return hit[1]
        val = build()
        if len(cache) >= 8:
            cache.pop(next(iter(cache)))
        cache[id(obj)] = (obj, val)
        return val

    def _plain_lane_tables(self, plan: base.FilterPlan):
        """((G, QB) lane→class names, class-member CSR) for one plan."""

        def build():
            class_of, lane_cls = _lane_classes(plan)
            valid = class_of >= 0
            order = np.argsort(class_of[valid], kind="stable")
            members = np.flatnonzero(valid)[order].astype(np.int32)
            n_cls = int(lane_cls.max(initial=-1)) + 1
            counts = np.bincount(class_of[valid], minlength=n_cls)
            offsets = np.concatenate(([0], np.cumsum(counts)))
            return lane_cls, offsets, members

        return self._lane_memo(plan, build)

    def _sharded_lane_tables(self, sharded):
        """Composed lane tables of a stacked sharded plan.

        Per-part accept classes get disjoint global ids (part-local id +
        running offset) and the member CSR stores **global subscriber
        ids** directly (tombstoned columns dropped at build time), so
        one device compaction over the folded ``(P·G·QB,)`` lane axis
        expands straight to (doc, gid) rows.  The lane table comes back
        ``(P, G, QB)`` so mesh paths can shard it over the part axis.
        """

        def build():
            gcols = sharded.gid_columns()
            lanes, member_parts, counts_parts = [], [], []
            off = 0
            for p, plan in enumerate(sharded.plans):
                class_of, lane_cls = _lane_classes(plan)
                n_cls = int(lane_cls.max(initial=-1)) + 1
                lanes.append(np.where(lane_cls >= 0, lane_cls + off, -1))
                valid = class_of >= 0
                order = np.argsort(class_of[valid], kind="stable")
                cols = np.flatnonzero(valid)[order]
                cls = class_of[valid][order]
                gids = gcols[p, cols]
                keep = gids >= 0          # drop tombstoned subscribers
                member_parts.append(gids[keep].astype(np.int32))
                counts_parts.append(
                    np.bincount(cls[keep], minlength=n_cls))
                off += n_cls
            counts = (np.concatenate(counts_parts)
                      if counts_parts else np.zeros(0, np.int64))
            offsets = np.concatenate(([0], np.cumsum(counts)))
            members = (np.concatenate(member_parts)
                       if member_parts else np.zeros(0, np.int32))
            return np.stack(lanes), offsets, members

        return self._lane_memo(sharded, build)

    def _fused_sparse_ok(self, cap: int) -> bool:
        """Run the in-kernel sparse epilogue for this cap?

        The ``sparse_epilogue=`` engine option forces it (``"on"`` /
        ``"off"``); ``"auto"`` (default) accepts whenever the bounded
        match buffer fits the epilogue VMEM budget — past that the
        two-launch lane compaction is the better trade.
        """
        mode = self.options.get("sparse_epilogue", "auto")
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"sparse_epilogue={mode!r} is not one of "
                f"('auto', 'on', 'off')")
        if mode != "auto":
            return mode == "on"
        return sf.epilogue_vmem_bytes(cap) <= DEFAULT_EPILOGUE_VMEM

    @staticmethod
    def _mark_base_path(sp: SparseResult) -> SparseResult:
        """Record that a sparse call left the kernel engine: the base
        class compacted (or densified) instead of the megakernel."""
        sp.meta["base_path"] = sp.meta.get("path")
        sp.meta["path"] = ("dense-overflow" if sp.overflowed
                           else "base-fallback")
        return sp

    def _expand_class_hits(self, bufs, count: int, cap: int, offsets,
                           members, *, batch_size: int, n_queries: int,
                           live_ids, meta: dict, dense_fallback,
                           overflowed: bool | None = None) -> SparseResult:
        """Device class-hit buffer → per-subscriber :class:`SparseResult`.

        Each compacted row names an accept class; ``offsets``/``members``
        is the class→subscriber CSR, expanded with one ``np.repeat`` —
        a row with k subscribers becomes k (doc, id) rows.  Overflow
        (``count > cap``, or the explicit flag from mesh paths whose
        per-device buffers each bound ``cap``) recomputes densely,
        exact but unbounded, and records ``path="dense-overflow"``.
        """
        over = (count > cap) if overflowed is None else bool(overflowed)
        if over:
            sp = dense_fallback().sparsify(live_ids)
            sp.overflowed = True
            sp.meta.update(meta, match_cap=cap, device_rows=int(count),
                           attempted_path=meta.get("path"),
                           path="dense-overflow")
            return sp
        docs, cls, first = (np.asarray(b)[:count] for b in bufs)
        meta = dict(meta, match_cap=cap, device_rows=int(docs.shape[0]))
        reps = (offsets[1:] - offsets[:-1])[cls]
        total = int(reps.sum())
        hit = np.repeat(np.arange(cls.shape[0]), reps)
        within = np.arange(total) - np.repeat(np.cumsum(reps) - reps, reps)
        qids = members[offsets[cls][hit] + within]
        docs, first = docs[hit], first[hit]
        order = np.lexsort((qids, docs))
        return SparseResult(
            docs[order], qids[order], first[order],
            batch_size=batch_size, n_queries=n_queries,
            live_ids=(None if live_ids is None
                      else np.asarray(live_ids, np.int32)),
            meta=meta)

    def filter_batch_sparse(self, batch: EventBatch, *,
                            match_cap: int | None = None) -> SparseResult:
        """Kernel engines emit the bounded match list straight from the
        megakernel (``path="kernel-fused"``: the accept bitmap never
        reaches HBM); caps past the epilogue VMEM budget keep the
        two-launch lane compaction (``"lane-compact"``); scan engines
        fall back to the base dense-verdict compaction
        (``"base-fallback"``).  All transfer O(cap), not O(B·Q)."""
        if not self._kernel_on():
            return self._mark_base_path(super().filter_batch_sparse(
                batch, match_cap=match_cap))
        kind, tag = self._prep(batch)
        lane_cls, offsets, members = self._plain_lane_tables(self.plan_)
        b = batch.batch_size
        cap = self.match_cap(b, self.n_queries, match_cap)
        if self._fused_sparse_ok(cap):
            doc_ids = jnp.arange(b, dtype=jnp.int32)[:, None]
            buf, cnt = _run_batch_kernel_fused(
                self.plan_, kind, tag, doc_ids, jnp.asarray(lane_cls),
                cap, interpret=self._kernel_interpret())
            bufs, n, over = _device_rows(buf, cnt, cap)
            path = "kernel-fused"
        else:
            *bufs, n = _run_batch_kernel_sparse(
                self.plan_, kind, tag,
                jnp.asarray(lane_cls.reshape(-1)), cap,
                interpret=self._kernel_interpret())
            n, over = int(n), None
            path = "lane-compact"
        return self._expand_class_hits(
            bufs, n, cap, offsets, members, batch_size=b,
            n_queries=self.n_queries, live_ids=None,
            meta={"path": path}, overflowed=over,
            dense_fallback=lambda: self.filter_batch(batch))

    def filter_batch_sharded_sparse(self, batch: EventBatch, sharded, *,
                                    mesh=None,
                                    match_cap: int | None = None
                                    ) -> SparseResult:
        """One megakernel launch (parts folded into the grid) straight
        into the bounded match buffer; classes expand to global
        subscriber ids on the host.  With a mesh the SAME fused program
        runs under ``shard_map`` over ``"model"`` — each device compacts
        its parts into its own bounded buffer (per-device cap), assembled
        on the host — instead of silently dropping to the base
        compaction; every route records ``meta["path"]``."""
        if not self._kernel_on():
            return self._mark_base_path(super().filter_batch_sharded_sparse(
                batch, sharded, mesh=mesh, match_cap=match_cap))
        kind, tag = self._prep(batch)
        lane_cls, offsets, members = self._sharded_lane_tables(sharded)
        live_ids = sharded.live_ids()
        b = batch.batch_size
        cap = self.match_cap(b, len(live_ids), match_cap)
        stacked = sharded.stacked()
        interpret = self._kernel_interpret()

        def dense_fallback():
            return self.filter_batch_sharded(batch, sharded, mesh=mesh)

        if not self._fused_sparse_ok(cap):
            *bufs, n = _run_parts_kernel_sparse(
                stacked, kind, tag, jnp.asarray(lane_cls.reshape(-1)),
                cap, interpret=interpret)
            return self._expand_class_hits(
                bufs, int(n), cap, offsets, members, batch_size=b,
                n_queries=len(live_ids), live_ids=live_ids,
                meta={"path": "lane-compact"},
                dense_fallback=dense_fallback)
        doc_ids = jnp.arange(b, dtype=jnp.int32)[:, None]
        if mesh is None:
            buf, cnt = _run_parts_kernel_fused(
                stacked, kind, tag, doc_ids, jnp.asarray(lane_cls), cap,
                interpret=interpret)
            bufs, n, over = _device_rows(buf, cnt, cap)
        else:
            self._check_model_axis(sharded, mesh)

            def build():
                def body(plan, kind, tag, doc_ids, lane):
                    return _run_parts_kernel_fused(
                        plan, kind, tag, doc_ids, lane, cap,
                        interpret=interpret)

                ps = jax.sharding.PartitionSpec
                return jax.jit(jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(ps("model"), ps(), ps(), ps(), ps("model")),
                    out_specs=(ps("model"), ps("model")),
                    check_vma=False))

            buf, cnt = self._cached_exec(
                ("1d-fused-sparse", mesh, cap), build)(
                stacked, kind, tag, doc_ids, jnp.asarray(lane_cls))
            bufs, n, over = _device_rows(buf, cnt, cap,
                                         mesh.shape["model"])
        return self._expand_class_hits(
            bufs, n, cap, offsets, members, batch_size=b,
            n_queries=len(live_ids), live_ids=live_ids,
            meta={"path": "kernel-fused"}, overflowed=over,
            dense_fallback=dense_fallback)

    def filter_batch_sharded2d_sparse(self, batch: EventBatch, sharded, *,
                                      mesh,
                                      match_cap: int | None = None
                                      ) -> SparseResult:
        """Sparse twin of the 2-D (data × model) dispatch: the fused
        epilogue runs INSIDE the shard_map body, so each device turns
        its "data" slice of documents × "model" slice of parts directly
        into a bounded match buffer — the previous host-side sparsify of
        the gathered dense result becomes the fallback route."""
        live_ids = sharded.live_ids()
        b0 = batch.batch_size
        cap = self.match_cap(b0, len(live_ids), match_cap)
        if not (self._kernel_on() and self._fused_sparse_ok(cap)):
            return self._mark_base_path(
                super().filter_batch_sharded2d_sparse(
                    batch, sharded, mesh=mesh, match_cap=match_cap))
        data_ax, _ = self._mesh_axes2d(mesh)
        self._check_model_axis(sharded, mesh)
        padded = batch.pad_batch_to(base._round_up(b0, data_ax))
        kind, tag = self._prep(padded)
        # pad documents carry no events — name them -1 so the kernel
        # drops them by construction rather than by accident
        ids = np.arange(padded.batch_size, dtype=np.int32)
        ids[b0:] = -1
        lane_cls, offsets, members = self._sharded_lane_tables(sharded)
        stacked = sharded.stacked()
        interpret = self._kernel_interpret()

        def build():
            def body(plan, kind, tag, doc_ids, lane):
                return _run_parts_kernel_fused(
                    plan, kind, tag, doc_ids, lane, cap,
                    interpret=interpret)

            ps = jax.sharding.PartitionSpec
            # bounded buffers stack device-major on axis 0 (one (cap, 3)
            # block per device of BOTH axes), unlike the dense 2-D path
            # whose (parts, docs) axes shard independently
            return jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(ps("model"), ps("data"), ps("data"),
                          ps("data"), ps("model")),
                out_specs=(ps(("model", "data")), ps(("model", "data"))),
                check_vma=False))

        buf, cnt = self._cached_exec(
            ("2d-fused-sparse", mesh, cap), build)(
            stacked, kind, tag, jnp.asarray(ids[:, None]),
            jnp.asarray(lane_cls))
        ndev = int(np.prod(list(mesh.shape.values())))
        bufs, n, over = _device_rows(buf, cnt, cap, ndev)
        return self._expand_class_hits(
            bufs, n, cap, offsets, members, batch_size=b0,
            n_queries=len(live_ids), live_ids=live_ids,
            meta={"path": "kernel-fused"}, overflowed=over,
            dense_fallback=lambda: self.filter_batch_sharded2d(
                batch, sharded, mesh=mesh))

    # ---------------------------------------------------------- byte paths
    def _fused_bytes_on(self) -> bool:
        """One-launch bytes kernel or the parse-then-filter program?
        The fused path needs the megakernel tables; ``fuse=False`` keeps
        the two-stage program (the comparison baseline)."""
        return self._kernel_on() and bool(self.options.get("fuse", True))

    def _bytes_prep(self, bb: ByteBatch, pack: bool | None = None
                    ) -> tuple[jax.Array, jax.Array, SegmentPack | None]:
        """(data, starts, pack-or-None) for the one-launch kernel.

        ``pack=True`` (or the ``pack=`` engine option) runs the host
        segment packer — short documents share grid slots; otherwise the
        batch maps 1:1 to degenerate one-document segments whose only
        boundary is the sentinel.
        """
        if pack is None:
            pack = bool(self.options.get("pack", False))
        if pack:
            sp = pack_segments(
                bb.to_host(),
                target_len=int(self.plan_.meta.get(
                    "segment_target", DEFAULT_SEGMENT_TARGET)))
            return jnp.asarray(sp.data), jnp.asarray(sp.starts), sp
        starts = np.full((bb.batch_size, 2), SEG_SENTINEL, np.int32)
        starts[:, 0] = 0
        return jnp.asarray(bb.data), jnp.asarray(starts), None

    def _scatter_parts(self, sp: SegmentPack | None, matched, first
                       ) -> tuple[np.ndarray, np.ndarray]:
        """(P, S, D, Qpad) kernel outputs → (P, B, Qpad) batch order."""
        m = np.asarray(matched)
        f = np.asarray(first)
        p, s, d, q = m.shape
        if sp is None:       # unpacked: segment s IS batch row s, D == 1
            return m[:, :, 0, :], f[:, :, 0, :]
        mm = np.moveaxis(m, 0, 2).reshape(s, d, p * q)
        ff = np.moveaxis(f, 0, 2).reshape(s, d, p * q)
        m2, f2 = sp.scatter(mm, ff, NO_MATCH)
        b = sp.batch_size
        return (m2.reshape(b, p, q).transpose(1, 0, 2),
                f2.reshape(b, p, q).transpose(1, 0, 2))

    def filter_bytes(self, bb: ByteBatch, *, bucket: int | None = None,
                     pack: bool | None = None) -> FilterResult:
        """Bytes → verdict as one compiled program.

        Kernel engines run the ONE-launch bytes megakernel
        (:func:`_run_bytes_fused` — predecode, compaction and filtering
        inside one Pallas grid, optionally over segment-packed batches);
        scan engines (and ``fuse=False``) run the two-stage
        parse-then-filter program (:func:`_run_bytes_batch`).  Both are
        bit-identical by test.
        """
        if not self._fused_bytes_on():
            matched, first = _run_bytes_batch(
                self.plan_, jnp.asarray(bb.data),
                bb.event_bound(bucket=self._event_bucket(bucket)),
                kernel=self._kernel_on(),
                interpret=self._kernel_interpret())
            return FilterResult(np.asarray(matched), np.asarray(first))
        data, starts, sp = self._bytes_prep(bb, pack)
        matched, first = _run_bytes_fused(
            self.plan_, data, starts, interpret=self._kernel_interpret())
        if sp is None:
            return FilterResult(np.asarray(matched[:, 0]),
                                np.asarray(first[:, 0]))
        m, f = sp.scatter(np.asarray(matched), np.asarray(first), NO_MATCH)
        return FilterResult(m, f)

    def filter_bytes_sharded(self, bb: ByteBatch, sharded, *,
                             bucket: int | None = None,
                             mesh=None) -> FilterResult:
        """Sharded bytes path: ONE fused launch for the whole stacked
        plan (parts fold into the block grid; ``shard_map`` over the
        mesh ``"model"`` axis when given), segment-packed when the
        ``pack=`` option is on.  Scan engines keep the base class's
        parse-then-filter program."""
        if not self._fused_bytes_on():
            return super().filter_bytes_sharded(bb, sharded,
                                                bucket=bucket, mesh=mesh)
        self._check_model_axis(sharded, mesh)
        data, starts, sp = self._bytes_prep(bb)
        stacked = sharded.stacked()
        interpret = self._kernel_interpret()

        def build():
            def body(plan, data, starts):
                return _run_parts_bytes_fused(plan, data, starts,
                                              interpret=interpret)

            if mesh is not None:
                ps = jax.sharding.PartitionSpec
                return jax.jit(jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(ps("model"), ps(), ps()),
                    out_specs=(ps("model"), ps("model")),
                    check_vma=False))
            return jax.jit(body)

        matched, first = self._cached_exec(
            ("bytes1d-fused", mesh), build)(stacked, data, starts)
        m, f = self._scatter_parts(sp, matched, first)
        part_of, local_of = sharded.index_arrays()
        return FilterResult(m[part_of, :, local_of].T,
                            f[part_of, :, local_of].T)

    def dispatch_bytes_sharded2d(self, bb: ByteBatch, sharded, *,
                                 bucket: int | None = None, mesh,
                                 n_events: int | None = None):
        """2-D (data × model) bytes path: the one-launch kernel inside
        the shard_map body — each device streams its ``"data"`` slice of
        raw segment bytes through its ``"model"`` slice of the stacked
        plan, bytes in / verdicts out with no intermediate event tensor
        anywhere in the program.  ``n_events`` is accepted for signature
        compatibility; the fused kernel is byte-chunked and never
        materializes a compacted event axis."""
        if not self._fused_bytes_on():
            return super().dispatch_bytes_sharded2d(
                bb, sharded, bucket=bucket, mesh=mesh, n_events=n_events)
        data_ax, _ = self._mesh_axes2d(mesh)
        self._check_model_axis(sharded, mesh)
        b0 = bb.batch_size
        if bool(self.options.get("pack", False)):
            sp = pack_segments(
                bb.to_host(),
                target_len=int(self.plan_.meta.get(
                    "segment_target", DEFAULT_SEGMENT_TARGET)))
            sp = sp.pad_segments_to(
                base._round_up(sp.n_segments, data_ax))
            data, starts = jnp.asarray(sp.data), jnp.asarray(sp.starts)
        else:
            sp = None
            bbp = bb.pad_batch_to(base._round_up(b0, data_ax))
            st = np.full((bbp.batch_size, 2), SEG_SENTINEL, np.int32)
            st[:, 0] = 0
            data, starts = jnp.asarray(bbp.data), jnp.asarray(st)
        stacked = sharded.stacked()
        interpret = self._kernel_interpret()

        def build():
            def body(plan, data, starts):
                return _run_parts_bytes_fused(plan, data, starts,
                                              interpret=interpret)

            ps = jax.sharding.PartitionSpec
            return jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(ps("model"), ps("data"), ps("data")),
                out_specs=(ps("model", "data"), ps("model", "data")),
                check_vma=False))

        matched, first = self._cached_exec(
            ("bytes2d-fused", mesh), build)(stacked, data, starts)
        part_of, local_of = sharded.index_arrays()

        def materialize() -> FilterResult:
            m, f = self._scatter_parts(sp, matched, first)
            return FilterResult(m[part_of, :, local_of].T[:b0],
                                f[part_of, :, local_of].T[:b0])

        return materialize

    def filter_bytes_sparse(self, bb: ByteBatch, *,
                            bucket: int | None = None,
                            match_cap: int | None = None,
                            pack: bool | None = None) -> SparseResult:
        """ONE launch raw bytes → bounded match list.

        The fused bytes megakernel ends in the in-kernel sparse
        epilogue: no event tensor AND no accept bitmap ever exist in
        HBM — the program's outputs are the ``(match_cap, 3)`` buffer
        and its counter (``path="kernel-fused"``, ``launch="bytes"``).
        Segment-packed batches ride along: ``doc_ids`` name each packed
        slot's original batch row (pads are ``-1``, dropped in-kernel).
        Non-kernel engines and oversized caps parse then route through
        :meth:`filter_batch_sparse`, which records its own path.

        The host's share is timed by spans (:mod:`repro.core.spans`)
        into ``meta``: ``launch_s`` (staging, H2D and the enqueue),
        ``device_s`` (blocking on the kernel and reading the match
        buffer back) and ``expand_s`` (class hits to subscribers).
        """
        b = bb.batch_size
        cap = self.match_cap(b, self.n_queries, match_cap)
        if not (self._fused_bytes_on() and self._fused_sparse_ok(cap)):
            return super().filter_bytes_sparse(bb, bucket=bucket,
                                               match_cap=match_cap)
        spans: dict = {}
        with span("xf.launch", spans):
            data, starts, spk = self._bytes_prep(bb, pack)
            doc_map = (spk.doc_ids if spk is not None
                       else np.arange(b, dtype=np.int32)[:, None])
            lane_cls, offsets, members = self._plain_lane_tables(self.plan_)
            buf, cnt = _run_bytes_fused_sparse(
                self.plan_, data, starts, jnp.asarray(doc_map),
                jnp.asarray(lane_cls), cap,
                interpret=self._kernel_interpret())
        with span("xf.device", spans):
            bufs, n, over = _device_rows(buf, cnt, cap)
        with span("xf.expand", spans):
            sp = self._expand_class_hits(
                bufs, n, cap, offsets, members, batch_size=b,
                n_queries=self.n_queries, live_ids=None,
                meta={"path": "kernel-fused", "launch": "bytes",
                      **_walk_counts(cnt)},
                overflowed=over,
                dense_fallback=lambda: self.filter_bytes(bb, pack=pack))
        sp.meta.update(spans)
        return sp

    def filter_bytes_sharded_sparse(self, bb: ByteBatch, sharded, *,
                                    bucket: int | None = None, mesh=None,
                                    match_cap: int | None = None
                                    ) -> SparseResult:
        """Sharded bytes → bounded match list, still ONE launch: parts
        fold into the block grid (or shard over the mesh ``"model"``
        axis, each device filling its own bounded buffer)."""
        live_ids = sharded.live_ids()
        b = bb.batch_size
        cap = self.match_cap(b, len(live_ids), match_cap)
        if not (self._fused_bytes_on() and self._fused_sparse_ok(cap)):
            return super().filter_bytes_sharded_sparse(
                bb, sharded, bucket=bucket, mesh=mesh,
                match_cap=match_cap)
        self._check_model_axis(sharded, mesh)
        stacked = sharded.stacked()
        data, starts, spk = self._bytes_prep(bb)
        doc_map = (spk.doc_ids if spk is not None
                   else np.arange(b, dtype=np.int32)[:, None])
        lane_cls, offsets, members = self._sharded_lane_tables(sharded)
        interpret = self._kernel_interpret()
        if mesh is None:
            buf, cnt = _run_parts_bytes_fused_sparse(
                stacked, data, starts, jnp.asarray(doc_map),
                jnp.asarray(lane_cls), cap, interpret=interpret)
            bufs, n, over = _device_rows(buf, cnt, cap)
        else:
            def build():
                def body(plan, data, starts, doc_map, lane):
                    return _run_parts_bytes_fused_sparse(
                        plan, data, starts, doc_map, lane, cap,
                        interpret=interpret)

                ps = jax.sharding.PartitionSpec
                return jax.jit(jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(ps("model"), ps(), ps(), ps(),
                              ps("model")),
                    out_specs=(ps("model"), ps("model")),
                    check_vma=False))

            buf, cnt = self._cached_exec(
                ("bytes1d-fused-sparse", mesh, cap), build)(
                stacked, data, starts, jnp.asarray(doc_map),
                jnp.asarray(lane_cls))
            bufs, n, over = _device_rows(buf, cnt, cap,
                                         mesh.shape["model"])
        return self._expand_class_hits(
            bufs, n, cap, offsets, members, batch_size=b,
            n_queries=len(live_ids), live_ids=live_ids,
            meta={"path": "kernel-fused", "launch": "bytes",
                  **_walk_counts(cnt)},
            overflowed=over,
            dense_fallback=lambda: self.filter_bytes_sharded(
                bb, sharded, mesh=mesh))

    def filter_documents_batched(self, kind: np.ndarray,
                                 tag: np.ndarray) -> FilterResult:
        """Legacy raw-array batched API (prefer :meth:`filter_batch`)."""
        matched, first = self._run_with_plan(
            self.plan_, (jnp.asarray(np.asarray(kind).astype(np.int32)),
                         jnp.asarray(tag)))
        return FilterResult(np.asarray(matched), np.asarray(first))
