"""Pallas megakernel: batched, bit-packed bytes→verdict streaming filter.

The closest TPU realization of the paper's architecture (Fig 5), and the
default device hot path of ``StreamingEngine``: the whole event→verdict
datapath runs as ONE fused kernel so that — exactly like the FPGA, where
parser and filter share a chip and every symbol advances all query blocks
in a single clock (§1, §3.2–3.4) — no per-event tensor ever leaves the
core.

Layout (see the README "Kernel hot path" diagram):

* **grid = (documents × state-word blocks)** — each program owns one
  document and one block of ≤BLK states *closed under parent pointers*
  (:func:`repro.kernels.blocks.state_layout` mirrors the paper's §3.3
  sort-and-cluster flow), so blocks never communicate — the property
  that lets the paper tile thousands of profiles.  Sharded plans fold
  their part axis into this block axis: more profiles are just more
  blocks, the paper's profiles-across-chips replication.
* **state = packed words on one vector row, end to end** — a block's
  ``WB = BLK/32`` state words sit in the lanes of one ``(1, 128)`` row
  (so ``BLK ≤ 4096``), and the document stack is a ``(max_depth+2,
  128)`` buffer of such rows in VMEM, the on-chip analogue of the FPGA's
  block-RAM tag stack (§3.2).  The per-event transition is a per-tag
  word-mask row load, an in-block parent gather done as a lane gather
  over a ``(32, 128)`` (bit × word) broadcast of the top-of-stack row, a
  sublane sum that packs the gathered bits back into words, and three
  bitwise ops — no per-event unpack of the stack, no matmul.
* **events stream through SMEM chunks** — each document's fused
  ``(kind<<16)|tag`` event words arrive as a lane-dense VMEM block; the
  kernel copies one chunk of ``(8, 128)`` tiles at a time into SMEM and
  walks it with the scalar core (the "8-bit streaming interface" of
  Fig 3), stopping at the document's last real event or byte.  The
  one-launch bytes kernel leaves its raw wire bytes in HBM and copies
  each chunk HBM→SMEM straight, the next chunk's copy in flight while
  the current one is walked, so a row of any length streams through a
  fixed few KB of fast memory.  Its scalar core stops only where a tag
  starts: every byte position is classified beforehand on the vector
  side into a tag-start bitmap (:func:`tag_bitmap`, one bit per byte),
  and the walk visits the bitmap's words and, in each, its set bits.

Per-block tables are stored by :mod:`repro.kernels.blocks` in their
canonical packed form (``(G, WB)`` words, ``(G, WB, 32)`` parent
indices, ``(G, QB)`` accept lanes); :func:`_kernel_tables` re-lays them
lane-dense for the kernel, so every ``BlockSpec`` covers whole trailing
array dimensions (Mosaic's tiling rule) and the block axis is squeezed.

Outputs per (document, block): the block's accept-lane verdict bits and
first-match event indices; the caller maps lanes back to queries (the
paper's priority encoder).

* **fused sparse epilogue** (``stream_filter_pallas_sparse`` /
  ``stream_filter_bytes_pallas_sparse``) — the sparse-delivery launch
  shape: instead of the dense ``(B, G, QB)`` accept bitmap, each program
  walks its own accept lanes at end-of-document and appends
  ``(doc_id, accept_class, first_event)`` entries to ONE bounded match
  buffer.  Cross-program coordination is a running SMEM counter in a
  constant-index-map output block: TPU grids execute *sequentially*, so
  reading the counter is a race-free exclusive scan over the grid — no
  atomics, and the only HBM traffic on the verdict side is
  O(match_cap), the paper's match-tuples-not-bitmaps delivery argument
  pushed all the way into the kernel.

Host oracles: :func:`repro.kernels.ref.stream_filter_words` (pure-jnp
scan of one word-block over the same packed tables — the unit-level
ground truth, tests/test_kernels.py asserts exact agreement) and the
``StreamingEngine`` ``lax.scan`` path (``kernel="scan"``, the end-to-end
oracle — tests/test_megakernel.py asserts the kernel is *bit-identical*
to it on ragged batches, churned plans and depth-overflow documents).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import parse as parse_mod
from . import ref
from .blocks import _round_up

NO_MATCH = jnp.iinfo(jnp.int32).max

#: fused event word: kind in the high half, tag (uint16 view) in the low
KIND_SHIFT = 16
TAG_MASK = 0xFFFF

#: vector lane width: a block's packed state words and every lane-dense
#: table row span one 128-lane row
LANES = 128
#: sublanes of one int32 vector tile: SMEM chunks are whole tiles
TILE_ROWS = 8
#: wire bytes carried by one row of int32 byte words
ROW_BYTES = 4 * LANES


def fuse_events(kind: jax.Array, tag: jax.Array) -> jax.Array:
    """(B, N) kind/tag → one int32 event word per event.

    One word per event means one SMEM scalar read per event inside the
    kernel.  PAD events keep working unchanged: their kind gates every
    state/stack/accept update off.
    """
    return ((kind.astype(jnp.int32) << KIND_SHIFT)
            | (tag.astype(jnp.int32) & TAG_MASK))


# ---------------------------------------------------------- table layout
def _kernel_tables(tagmask, pw, pb, selfloop_words, init_words, acc_word,
                   acc_bit, lane_cls=None) -> tuple[list, dict]:
    """Canonical packed block tables → the kernel's lane-dense layout.

    Words move to the 128-lane axis (``WB ≤ 128``); parent word/bit
    indices transpose to ``(G, 32, 128)`` (bit × word) so one lane
    gather serves all 32 bits of every word; accept lanes fold to
    ``(G, QR, 128)``.  Pad lanes point at local state 0 — every block's
    root replica, whose bit never sets (NEVER in-tag, no self-loop) — so
    they are inert by construction.  Returns the tables and their dims.
    """
    g, wb = selfloop_words.shape
    qb = acc_word.shape[1]
    if wb > LANES:
        raise ValueError(
            f"block of {wb} words exceeds one {LANES}-lane row "
            f"(blk ≤ {LANES * 32})")
    qr = _round_up(-(-qb // LANES), TILE_ROWS)

    def words(x):                                  # (G, ..., WB) → int32
        x = jax.lax.bitcast_convert_type(x, jnp.int32) \
            if x.dtype == jnp.uint32 else x.astype(jnp.int32)
        pad = [(0, 0)] * (x.ndim - 1) + [(0, LANES - wb)]
        return jnp.pad(x, pad)

    def lanes(x, fill):                            # (G, QB) → (G, QR, 128)
        x = jnp.pad(x.astype(jnp.int32), ((0, 0), (0, qr * LANES - qb)),
                    constant_values=fill)
        return x.reshape(g, qr, LANES)

    tabs = [words(tagmask),
            words(jnp.swapaxes(pw, 1, 2)),
            words(jnp.swapaxes(pb, 1, 2)),
            words(selfloop_words)[:, None, :],
            words(init_words)[:, None, :],
            lanes(acc_word, 0), lanes(acc_bit, 0)]
    if lane_cls is not None:
        tabs.append(lanes(lane_cls, -1))
    return tabs, dict(g=g, qb=qb, qr=qr, n_tags=tagmask.shape[1] - 1)


def _table_specs(by_block, dims: dict, *, with_cls: bool = False) -> list:
    """BlockSpecs of :func:`_kernel_tables`: one squeezed block each."""
    def spec(rows):
        return pl.BlockSpec((None, rows, LANES),
                            lambda *ids: by_block(*ids) + (0, 0))

    qr = dims["qr"]
    out = [spec(dims["n_tags"] + 1), spec(32), spec(32), spec(1), spec(1),
           spec(qr), spec(qr)]
    return out + [spec(qr)] if with_cls else out


def _chunk_rows(chunk: int, per_row: int) -> int:
    """Rows of one SMEM chunk holding ``chunk`` items (events or
    bytes) at ``per_row`` items per 128-lane row, in whole tiles."""
    return _round_up(-(-max(int(chunk), 1) // per_row), TILE_ROWS)


def _smem_full():
    """Whole small int32 vector in SMEM (per-row counts, boundaries)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _block_tables(tagmask_ref, pw_ref, pb_ref, self_ref, accw_ref,
                  accb_ref):
    """Load this program's block tables once, before the event loop."""
    return dict(
        pw=pw_ref[...],          # (32, 128) parent word per (bit, word)
        pb=pb_ref[...],          # (32, 128) parent bit per (bit, word)
        selfw=self_ref[...],     # (1, 128) packed self-loop states
        accw=accw_ref[...],      # (QR, 128) accept-lane word
        accb=accb_ref[...],      # (QR, 128) accept-lane bit
        tagmask_ref=tagmask_ref,
        bit=jax.lax.broadcasted_iota(jnp.int32, (32, LANES), 0))


def _lane_init(qr: int):
    """(matched, first) accept-lane carry of a fresh document."""
    return (jnp.zeros((qr, LANES), jnp.int32),
            jnp.full((qr, LANES), NO_MATCH, jnp.int32))


def _advance(ev, i, depth, matched, first, stack_ref, tb, *,
             max_depth: int, n_tags: int):
    """One fused event word through one state-word block.

    THE per-event transition, shared verbatim by the event-stream kernel
    (:func:`stream_filter_pallas`) and the one-launch bytes kernel
    (:func:`stream_filter_bytes_pallas`) — one definition, so the two
    launch shapes can never drift apart semantically.  ``i`` is the
    document-local event ordinal reported as the first-match index.
    """
    k = ev >> KIND_SHIFT
    t = ev & TAG_MASK
    is_open = k == ref.OPEN
    is_close = k == ref.CLOSE
    row = stack_ref[pl.ds(depth, 1), :]                  # (1, 128) TOS
    tclip = jnp.where((t >= 0) & (t < n_tags), t, n_tags)
    trow = tb["tagmask_ref"][pl.ds(tclip, 1), :]         # per-tag words
    # in-block parent gather, packed → packed: lane-gather each state's
    # parent word, pick its bit, and sum the 32 bit-rows back into words
    par = jnp.take_along_axis(jnp.broadcast_to(row, (32, LANES)),
                              tb["pw"], axis=1, mode="promise_in_bounds")
    bits = jax.lax.shift_right_logical(par, tb["pb"]) & 1
    src = jnp.sum(bits << tb["bit"], axis=0, keepdims=True)
    nxt = (src & trow) | (tb["selfw"] & row)
    # push on open (write at depth+1), no-op otherwise — exactly the
    # scan path's clip discipline, so depth overflow degrades
    # identically on both paths
    widx = jnp.clip(depth + 1, 0, max_depth + 1)
    old = stack_ref[pl.ds(widx, 1), :]
    stack_ref[pl.ds(widx, 1), :] = jnp.where(is_open, nxt, old)
    depth = jnp.clip(
        depth + jnp.where(is_open, 1, jnp.where(is_close, -1, 0)),
        0, max_depth + 1)
    qr = tb["accw"].shape[0]
    acc = jnp.take_along_axis(jnp.broadcast_to(nxt, (qr, LANES)),
                              tb["accw"], axis=1, mode="promise_in_bounds")
    active = (jax.lax.shift_right_logical(acc, tb["accb"]) & 1) \
        * is_open.astype(jnp.int32)
    first = jnp.where((active != 0) & (matched == 0), i, first)
    matched = matched | active
    return depth, matched, first


def _to_smem(src, dst_ref, sem_ref):
    """Synchronous VMEM → SMEM copy of one (8, 128) tile."""
    cp = pltpu.make_async_copy(src, dst_ref, sem_ref.at[0])
    cp.start()
    cp.wait()


def _reset_stack(stack_ref, init_row):
    stack_ref[...] = jnp.zeros_like(stack_ref)
    stack_ref[pl.ds(0, 1), :] = init_row


def _stream_events(ev_ref, n_ev, evbuf_ref, sem_ref, stack_ref, tb, *,
                   max_depth: int, n_tags: int, qr: int):
    """Event loop of ONE (document, block) program.

    Shared by the dense kernel (:func:`_kernel`) and the fused-sparse
    kernel (:func:`_kernel_sparse`) so the two launch shapes can never
    drift: copy this document's fused event words VMEM→SMEM one chunk
    at a time and run :func:`_advance` per event, up to the document's
    ``n_ev`` real events (the PAD tail is inert and skipped).  Returns
    (matched, first) for the block's accept lanes.
    """
    rows = evbuf_ref.shape[0]
    chunk = rows * LANES

    def chunk_body(ci, carry):
        _to_smem(ev_ref.at[0, pl.ds(pl.multiple_of(ci * rows, TILE_ROWS),
                                    rows), :], evbuf_ref, sem_ref)
        base = ci * chunk

        def ev_body(j, carry):
            depth, matched, first = carry
            return _advance(evbuf_ref[j >> 7, j & (LANES - 1)], base + j,
                            depth, matched, first, stack_ref, tb,
                            max_depth=max_depth, n_tags=n_tags)

        return jax.lax.fori_loop(0, jnp.minimum(chunk, n_ev - base),
                                 ev_body, carry)

    _, matched, first = jax.lax.fori_loop(
        0, (n_ev + chunk - 1) // chunk, chunk_body,
        (jnp.int32(0),) + _lane_init(qr))
    return matched, first


def _kernel(n_ev_ref, ev_ref, tagmask_ref, pw_ref, pb_ref, self_ref,
            init_ref, accw_ref, accb_ref, matched_ref, first_ref,
            stack_ref, evbuf_ref, sem_ref, *, max_depth: int, n_tags: int,
            doc_axis: int):
    b = pl.program_id(doc_axis)
    qr = accw_ref.shape[0]
    # fresh document: zero the VMEM stack, root context at depth 0
    _reset_stack(stack_ref, init_ref[...])
    tb = _block_tables(tagmask_ref, pw_ref, pb_ref, self_ref, accw_ref,
                       accb_ref)
    matched, first = _stream_events(
        ev_ref, n_ev_ref[b], evbuf_ref, sem_ref, stack_ref, tb,
        max_depth=max_depth, n_tags=n_tags, qr=qr)
    matched_ref[...] = matched
    first_ref[...] = first


# ------------------------------------------------- fused sparse epilogue
def _sparse_init(out_ref, cnt_ref):
    """First grid step: empty the shared match buffer and the counter.

    Both live in constant-index-map output blocks, so they stay resident
    on core across every grid step (TPU grids run *sequentially*) and
    flush to HBM exactly once, after the last step — the property that
    makes a running SMEM counter a race-free exclusive scan over the
    whole grid, with no atomics.
    """

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[0] = jnp.full(out_ref.shape[1:], -1, jnp.int32)
        out_ref[1] = jnp.full(out_ref.shape[1:], -1, jnp.int32)
        out_ref[2] = jnp.full(out_ref.shape[1:], NO_MATCH, jnp.int32)
        for j in range(cnt_ref.shape[1]):
            cnt_ref[0, j] = 0


def _emit_rows(matched, first, cls, doc, out_ref, cnt_ref, stage_ref,
               stage_sm, sem_ref, *, cap: int):
    """End-of-document epilogue of ONE program: append this block's
    accept lanes to the shared bounded match buffer.

    ``matched``/``first``/``cls`` are the block's ``(QR, 128)`` lane
    outputs and accept-class names (``-1`` = inert lane); ``doc`` the
    global document id (``< 0`` = unused slot, dropped).  The hit
    classes and first-match indices are staged VMEM→SMEM, and the
    scalar core walks the lanes in order, writing each hit as entry
    ``count`` — field ``f`` of entry ``e`` lives at ``out[f, e // 128,
    e % 128]``.  Reading the counter IS this program's slice of the
    cross-grid exclusive scan (see :func:`_sparse_init`).  Writes stop
    at ``cap`` while the counter keeps the TRUE total — ``count > cap``
    is the caller's overflow signal.
    """
    qr = matched.shape[0]
    hit = (matched != 0) & (cls >= 0)
    nv = jnp.sum(hit.astype(jnp.int32))

    @pl.when((nv > 0) & (doc >= 0))
    def _():
        stage_ref[0:qr, :] = jnp.where(hit, cls, -1)
        stage_ref[qr:2 * qr, :] = first
        _to_smem(stage_ref, stage_sm, sem_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def put(f, r, at, val):
            old = out_ref[f, pl.ds(r, 1), :]
            out_ref[f, pl.ds(r, 1), :] = jnp.where(lane == at, val, old)

        def lane_body(j, cnt):
            c = stage_sm[j >> 7, j & (LANES - 1)]

            @pl.when((c >= 0) & (cnt < cap))
            def _():
                r, at = cnt >> 7, cnt & (LANES - 1)
                put(0, r, at, doc)
                put(1, r, at, c)
                put(2, r, at, stage_sm[qr + (j >> 7), j & (LANES - 1)])

            return cnt + (c >= 0).astype(jnp.int32)

        cnt_ref[0, 0] = jax.lax.fori_loop(0, qr * LANES, lane_body,
                                          cnt_ref[0, 0])


def _kernel_sparse(n_ev_ref, docid_ref, ev_ref, tagmask_ref, pw_ref,
                   pb_ref, self_ref, init_ref, accw_ref, accb_ref,
                   lane_ref, out_ref, cnt_ref, stack_ref, evbuf_ref,
                   stage_ref, stage_sm, sem_ref, *, max_depth: int,
                   n_tags: int, doc_axis: int, cap: int):
    """Sparse twin of :func:`_kernel`: same streamed transition, but the
    per-(document, block) accept lanes are appended to the bounded match
    buffer at end-of-document and only that buffer ever reaches HBM."""
    b = pl.program_id(doc_axis)
    qr = accw_ref.shape[0]
    _sparse_init(out_ref, cnt_ref)
    _reset_stack(stack_ref, init_ref[...])
    tb = _block_tables(tagmask_ref, pw_ref, pb_ref, self_ref, accw_ref,
                       accb_ref)
    matched, first = _stream_events(
        ev_ref, n_ev_ref[b], evbuf_ref, sem_ref, stack_ref, tb,
        max_depth=max_depth, n_tags=n_tags, qr=qr)
    _emit_rows(matched, first, lane_ref[...], docid_ref[b], out_ref,
               cnt_ref, stage_ref, stage_sm, sem_ref, cap=cap)


#: megakernel grid iteration orders — ``"bg"`` walks documents in the
#: outer loop (block tables re-streamed per document), ``"gb"`` walks
#: blocks outermost (each block's tables stay resident across the whole
#: batch).  Which wins depends on (batch, n_blocks, table bytes) — an
#: autotune dimension (:mod:`repro.kernels.autotune`), not a constant.
GRID_ORDERS = ("bg", "gb")


def _grid_maps(grid_order: str, bsz: int, g: int):
    """(grid, doc_axis, by-block index map, by-doc index map)."""
    if grid_order not in GRID_ORDERS:
        raise ValueError(
            f"grid_order={grid_order!r} is not one of {GRID_ORDERS}")
    if grid_order == "gb":
        return ((g, bsz), 1, lambda gg, b: (gg,), lambda gg, b: (b,))
    return ((bsz, g), 0, lambda b, gg: (gg,), lambda b, gg: (b,))


def _event_rows(events: jax.Array, rows: int
                ) -> tuple[jax.Array, jax.Array]:
    """(B, N) fused events → ((B, R, 128) PAD-padded chunks of ``rows``
    rows, (B,) counts).

    The count is one past each document's last non-PAD event, so the
    kernel stops there instead of stepping through the inert tail.
    """
    bsz, n = events.shape
    npad = _round_up(n, rows * LANES)
    events = jnp.pad(events, ((0, 0), (0, npad - n)),
                     constant_values=ref.PAD << KIND_SHIFT)
    real = (events >> KIND_SHIFT) != ref.PAD
    pos = jax.lax.broadcasted_iota(jnp.int32, events.shape, 1)
    n_ev = jnp.max(jnp.where(real, pos + 1, 0), axis=1).astype(jnp.int32)
    return events.reshape(bsz, npad // LANES, LANES), n_ev


def _lane_out(x: jax.Array, qb: int) -> jax.Array:
    """(..., QR, 128) kernel lane blocks → (..., QB)."""
    return x.reshape(x.shape[:-2] + (-1,))[..., :qb]


def _buffer_rows(cap: int) -> int:
    return _round_up(-(-max(int(cap), 1) // LANES), TILE_ROWS)


def epilogue_vmem_bytes(cap: int) -> int:
    """VMEM the fused epilogue's resident match buffer takes for ``cap``
    entries: three int32 fields, lane-dense."""
    return 3 * _buffer_rows(cap) * LANES * 4


def _match_list(out: jax.Array, cap: int) -> jax.Array:
    """(3, R, 128) field-major match buffer → (cap, 3) rows."""
    return out.reshape(3, -1)[:, :cap].T


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "chunk", "interpret",
                                    "grid_order"))
def stream_filter_pallas(events: jax.Array, tagmask: jax.Array,
                         pw: jax.Array, pb: jax.Array,
                         selfloop_words: jax.Array, init_words: jax.Array,
                         acc_word: jax.Array, acc_bit: jax.Array, *,
                         max_depth: int, chunk: int = 256,
                         interpret: bool | None = None,
                         grid_order: str = "bg"
                         ) -> tuple[jax.Array, jax.Array]:
    """Run every (document × state-word block) over the event stream.

    events (B, N) int32 fused words (:func:`fuse_events`); block tables
    as emitted by :func:`repro.kernels.blocks.state_layout`: tagmask
    (G, T+1, WB) uint32, pw/pb (G, WB, 32) int32, selfloop/init words
    (G, WB) uint32, acc_word/acc_bit (G, QB) int32.  ``max_depth`` is
    the *plan's* stack bound — callers thread it from plan metadata so
    kernel and scan can never disagree.  Returns matched (B, G, QB)
    int32 0/1 and first (B, G, QB) int32 accept-lane outputs.
    ``interpret=None`` auto-detects from the backend; ``grid_order``
    picks the grid iteration order (:data:`GRID_ORDERS`); ``chunk`` is
    events per SMEM chunk, rounded up to whole ``(8, 128)`` tiles.
    """
    from . import interpret_default

    if interpret is None:
        interpret = interpret_default()
    bsz = events.shape[0]
    tabs, dims = _kernel_tables(tagmask, pw, pb, selfloop_words,
                                init_words, acc_word, acc_bit)
    rows = _chunk_rows(chunk, LANES)
    ev, n_ev = _event_rows(events, rows)
    g, qr = dims["g"], dims["qr"]
    grid, doc_axis, by_block, by_doc = _grid_maps(grid_order, bsz, g)
    out_spec = pl.BlockSpec(
        (None, None, qr, LANES),
        lambda *ids: by_doc(*ids) + by_block(*ids) + (0, 0))
    matched, first = pl.pallas_call(
        functools.partial(_kernel, max_depth=max_depth,
                          n_tags=dims["n_tags"], doc_axis=doc_axis),
        grid=grid,
        in_specs=[_smem_full(),
                  pl.BlockSpec((1,) + ev.shape[1:],
                               lambda *ids: by_doc(*ids) + (0, 0))]
        + _table_specs(by_block, dims),
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((bsz, g, qr, LANES), jnp.int32)] * 2,
        scratch_shapes=[
            # the paper's block-RAM tag stack: packed words in VMEM
            pltpu.VMEM((max_depth + 2, LANES), jnp.int32),
            # one event chunk in SMEM (the streaming interface)
            pltpu.SMEM((rows, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret,
        name="stream_filter_pallas",
    )(n_ev, ev, *tabs)
    return _lane_out(matched, dims["qb"]), _lane_out(first, dims["qb"])


@functools.partial(jax.jit,
                   static_argnames=("cap", "max_depth", "chunk",
                                    "interpret", "grid_order"))
def stream_filter_pallas_sparse(events: jax.Array, doc_ids: jax.Array,
                                tagmask: jax.Array, pw: jax.Array,
                                pb: jax.Array, selfloop_words: jax.Array,
                                init_words: jax.Array, acc_word: jax.Array,
                                acc_bit: jax.Array, lane_cls: jax.Array, *,
                                cap: int, max_depth: int, chunk: int = 256,
                                interpret: bool | None = None,
                                grid_order: str = "bg"
                                ) -> tuple[jax.Array, jax.Array]:
    """One launch events → bounded match list: the fused sparse epilogue.

    Same grid and tables as :func:`stream_filter_pallas`, but the
    ``(B, G, QB)`` accept bitmap never leaves VMEM: each program appends
    its own accept lanes at end-of-document to a single shared bounded
    buffer of ``(doc_id, accept_class, first_event)`` entries,
    coordinated by a running SMEM counter that the sequential TPU grid
    turns into an exclusive scan (no atomics).  ``doc_ids`` (B, 1) int32
    names each batch row globally (``< 0`` drops the row — segment
    pads); ``lane_cls`` (G, QB) int32 names each lane's accept class
    (``-1`` = inert).  Returns ``(buf, count)`` where ``buf`` is
    ``(cap, 3)``, only ``buf[:min(count, cap)]`` rows are valid and
    ``count > cap`` signals overflow; row order is grid emission order,
    not sorted.
    """
    from . import interpret_default

    if interpret is None:
        interpret = interpret_default()
    bsz = events.shape[0]
    tabs, dims = _kernel_tables(tagmask, pw, pb, selfloop_words,
                                init_words, acc_word, acc_bit, lane_cls)
    rows = _chunk_rows(chunk, LANES)
    ev, n_ev = _event_rows(events, rows)
    g, qr = dims["g"], dims["qr"]
    brows = _buffer_rows(cap)
    grid, doc_axis, by_block, by_doc = _grid_maps(grid_order, bsz, g)
    out, cnt = pl.pallas_call(
        functools.partial(_kernel_sparse, max_depth=max_depth,
                          n_tags=dims["n_tags"], doc_axis=doc_axis,
                          cap=int(cap)),
        grid=grid,
        in_specs=[_smem_full(), _smem_full(),
                  pl.BlockSpec((1,) + ev.shape[1:],
                               lambda *ids: by_doc(*ids) + (0, 0))]
        + _table_specs(by_block, dims, with_cls=True),
        out_specs=[
            # constant index maps: the match buffer and counter persist
            # on core across the WHOLE grid and flush to HBM once
            pl.BlockSpec((3, brows, LANES), lambda *ids: (0, 0, 0)),
            pl.BlockSpec((1, 1), lambda *ids: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((3, brows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((max_depth + 2, LANES), jnp.int32),
            pltpu.SMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((2 * qr, LANES), jnp.int32),
            pltpu.SMEM((2 * qr, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret,
        name="stream_filter_pallas_sparse",
    )(n_ev, doc_ids.reshape(-1).astype(jnp.int32), ev, *tabs)
    return _match_list(out, int(cap)), cnt


# ----------------------------------------------- one-launch bytes kernel
def _bytes_stream(data_ref, bm_ref, seg, n_bytes, starts_ref, st0,
                  stack_ref, mbuf_ref, fbuf_ref, bbuf_ref, bmbuf_ref,
                  sem_ref, tb, init_row, *, max_depth: int, n_tags: int,
                  qr: int):
    """Streaming body of the one-launch bytes kernel, one grid cell.

    Shared verbatim by the dense (:func:`_bytes_kernel`) and
    fused-sparse (:func:`_bytes_kernel_sparse`) launch shapes.  Segment
    ``seg``'s int32-packed bytes and its tag-start bitmap
    (:func:`tag_bitmap`, one bit per byte, made on the vector side
    before the launch) stay in HBM; the walk copies them HBM→SMEM one
    chunk at a time (the chunk's bytes plus one lookahead tile, so tags
    straddling the boundary decode whole, and the chunk's bitmap words)
    into one of two slots, and starts the copy of chunk ``ci + 1`` into
    the other slot before it walks chunk ``ci``.  So no buffer grows
    with the row, and the copies hide behind the walk.  The scalar core
    walks the bitmap words, not the bytes: an empty word is one load and
    one compare; a non-empty one gives up its set bits lowest first
    (``w & -w`` isolates one, ``w & (w - 1)`` clears it; bit 31 makes
    the word negative, which neither minds).  Each set bit is a tag
    start: the §3.4 character pre-decode
    (:func:`repro.kernels.parse.fused_predecode`, the same function the
    vector parser uses) runs on its byte and three lookahead bytes, and
    the tag becomes one event through the shared :func:`_advance`
    transition.  The walk stops at the segment's last non-zero byte
    (``n_bytes``): zero padding starts no tag.

    The ``starts`` table (one boundary row per segment at flat offset
    ``st0``, INT32_MAX sentinel past the last doc) drives per-document
    resets: crossing a boundary flushes the finished document's accept
    lanes to the (D, QR, 128) result buffers and re-roots the stack —
    this is how short documents share a grid slot instead of padding to
    the longest.  On return every document row of ``mbuf_ref`` /
    ``fbuf_ref`` is final, every copy has landed, and the result is the
    number of chunks the walk fetched.
    """
    # result buffers for every document in this segment; empty doc slots
    # keep these initial values (flushed by the boundary loop unchanged)
    mbuf_ref[...] = jnp.zeros_like(mbuf_ref)
    fbuf_ref[...] = jnp.full_like(fbuf_ref, NO_MATCH)
    _reset_stack(stack_ref, init_row)

    rows = bbuf_ref.shape[1] - TILE_ROWS
    chunk = rows * ROW_BYTES
    bm_rows = bmbuf_ref.shape[1]
    n_chunks = (n_bytes + chunk - 1) // chunk

    def fetch(ci, slot):
        # chunk ci's bytes (+ lookahead tile) and bitmap words → slot
        return (
            pltpu.make_async_copy(
                data_ref.at[seg, pl.ds(pl.multiple_of(ci * rows, TILE_ROWS),
                                       rows + TILE_ROWS), :],
                bbuf_ref.at[slot], sem_ref.at[slot]),
            pltpu.make_async_copy(
                bm_ref.at[seg, pl.ds(pl.multiple_of(ci * bm_rows, TILE_ROWS),
                                     bm_rows), :],
                bmbuf_ref.at[slot], sem_ref.at[2 + slot]))

    @pl.when(n_chunks > 0)
    def _():
        for cp in fetch(0, 0):
            cp.start()

    def chunk_body(ci, carry):
        slot = ci & 1

        @pl.when(ci + 1 < n_chunks)
        def _():
            for cp in fetch(ci + 1, 1 - slot):
                cp.start()

        for cp in fetch(ci, slot):
            cp.wait()
        base = ci * chunk

        def byte_at(q):
            # byte q of the staged int32 words, little-endian
            w = bbuf_ref[slot, q >> 9, (q >> 2) & (LANES - 1)]
            return jax.lax.shift_right_logical(w, (q & 3) * 8) & 0xFF

        def on_event(p, fused, carry):
            d, nxt, depth, doc0, ord_, matched, first = carry
            pos = base + p

            # crossed one or more doc boundaries? flush and re-root.
            # ``nxt`` (the next boundary offset) rides in the carry so
            # the while cond stays ref-free; sentinel rows past the
            # last real document make it +inf-like, never crossed.
            def flush_body(c):
                dd, _, _, _, oo, mm, ff = c
                mbuf_ref[dd] = mm
                fbuf_ref[dd] = ff
                stack_ref[pl.ds(0, 1), :] = init_row
                return (dd + 1, starts_ref[st0 + dd + 2], jnp.int32(0),
                        oo, oo) + _lane_init(qr)

            d, nxt, depth, doc0, ord_, matched, first = jax.lax.while_loop(
                lambda c: pos >= c[1], flush_body,
                (d, nxt, depth, doc0, ord_, matched, first))
            depth, matched, first = _advance(
                fused, ord_ - doc0, depth, matched, first, stack_ref, tb,
                max_depth=max_depth, n_tags=n_tags)
            return d, nxt, depth, doc0, ord_ + 1, matched, first

        def word_body(k, carry):
            def bit_body(c):
                w, carry = c[0], c[1:]
                # the lowest set bit's index: 31 - clz of ``w & -w``
                p = (k << 5) + 31 - jax.lax.clz(w & -w)
                fused, _ = parse_mod.fused_predecode(
                    *(byte_at(p + i) for i in range(4)))
                return (w & (w - 1),) + on_event(p, fused, carry)

            return jax.lax.while_loop(
                lambda c: c[0] != 0, bit_body,
                (bmbuf_ref[slot, k >> 7, k & (LANES - 1)],) + carry)[1:]

        return jax.lax.fori_loop(
            0, jnp.minimum(chunk, n_bytes - base + 31) >> 5, word_body,
            carry)

    d, _, _, _, _, matched, first = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (jnp.int32(0), starts_ref[st0 + 1], jnp.int32(0), jnp.int32(0),
         jnp.int32(0)) + _lane_init(qr))
    # flush the document the stream ended inside; remaining (empty) doc
    # slots keep their initial rows
    mbuf_ref[d] = matched
    fbuf_ref[d] = first
    return n_chunks


def _bytes_kernel(n_bytes_ref, starts_ref, data_ref, bm_ref, tagmask_ref,
                  pw_ref, pb_ref, self_ref, init_ref, accw_ref, accb_ref,
                  matched_ref, first_ref, stack_ref, mbuf_ref, fbuf_ref,
                  bbuf_ref, bmbuf_ref, sem_ref, *, max_depth: int,
                  n_tags: int, n_docs: int, doc_axis: int):
    """One-launch bytes→verdict (dense): stream, then copy the per-doc
    accept-lane rows out (see :func:`_bytes_stream`)."""
    s = pl.program_id(doc_axis)
    qr = accw_ref.shape[0]
    tb = _block_tables(tagmask_ref, pw_ref, pb_ref, self_ref, accw_ref,
                       accb_ref)
    _bytes_stream(data_ref, bm_ref, s, n_bytes_ref[s], starts_ref,
                  s * (n_docs + 1), stack_ref, mbuf_ref, fbuf_ref, bbuf_ref,
                  bmbuf_ref, sem_ref, tb, init_ref[...],
                  max_depth=max_depth, n_tags=n_tags, qr=qr)
    matched_ref[...] = mbuf_ref[...]
    first_ref[...] = fbuf_ref[...]


def _bytes_kernel_sparse(n_bytes_ref, starts_ref, docmap_ref, data_ref,
                         bm_ref, tagmask_ref, pw_ref, pb_ref, self_ref,
                         init_ref, accw_ref, accb_ref, lane_ref, out_ref,
                         cnt_ref, stack_ref, mbuf_ref, fbuf_ref, bbuf_ref,
                         bmbuf_ref, sem_ref, stage_ref, stage_sm, *,
                         max_depth: int, n_tags: int, n_docs: int,
                         doc_axis: int, cap: int):
    """Sparse twin of :func:`_bytes_kernel`: after the stream, every
    document row of the segment is appended to the shared bounded match
    buffer (``docmap`` names each slot's global batch row; ``-1`` pad
    slots emit nothing).  The counter's second column sums the chunks
    every program's walk fetched."""
    s = pl.program_id(doc_axis)
    qr = accw_ref.shape[0]
    _sparse_init(out_ref, cnt_ref)
    tb = _block_tables(tagmask_ref, pw_ref, pb_ref, self_ref, accw_ref,
                       accb_ref)
    cnt_ref[0, 1] += _bytes_stream(
        data_ref, bm_ref, s, n_bytes_ref[s], starts_ref, s * (n_docs + 1),
        stack_ref, mbuf_ref, fbuf_ref, bbuf_ref, bmbuf_ref, sem_ref, tb,
        init_ref[...], max_depth=max_depth, n_tags=n_tags, qr=qr)
    cls = lane_ref[...]

    def doc_body(dd, carry):
        _emit_rows(mbuf_ref[dd], fbuf_ref[dd], cls,
                   docmap_ref[s * n_docs + dd], out_ref, cnt_ref,
                   stage_ref, stage_sm, sem_ref, cap=cap)
        return carry

    jax.lax.fori_loop(0, n_docs, doc_body, jnp.int32(0))


def tag_bitmap(data: jax.Array) -> jax.Array:
    """(S, L) uint8 rows → (S, ⌈L/32⌉) int32 tag-start bitmap.

    Bit ``j`` of word ``k`` is set where a valid tag starts at byte
    ``32k + j``: the positions that the pre-decoder
    (:func:`repro.kernels.ref.predecode`, bit-identical to
    :func:`repro.kernels.parse.fused_predecode`) keeps, with zeros
    shifted in past the row's end.  Every position is classified at
    once, on the vector side, so the bytes kernel's scalar walk visits
    only the bytes where a tag starts.
    """
    nseg, length = data.shape
    data = jnp.pad(data, ((0, 0), (0, -length % 32)))
    kind, _ = ref.predecode(data)
    bits = (kind != ref.PAD).astype(jnp.uint32).reshape(nseg, -1, 32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _bitmap_rows(rows: int) -> int:
    """Rows of one chunk's bitmap block: a chunk of ``rows`` byte rows
    has ``rows * 16`` bitmap words, in whole ``(8, 128)`` tiles."""
    return _round_up(rows * ROW_BYTES // 32 // LANES, TILE_ROWS)


def _byte_rows(data: jax.Array, rows: int
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(S, L) uint8 → the bytes kernel's inputs.

    Returns ``(words, bitmap, ends, tag_starts)``: ``words`` (S, R, 128)
    little-endian int32 byte words, the rows padded to whole chunks of
    ``rows`` rows plus one spare tile of zeros (the lookahead of the
    last chunk); ``bitmap`` (S, C · bm_rows, 128) the :func:`tag_bitmap`
    words of each of the C chunks, one block of :func:`_bitmap_rows`
    rows per chunk; ``ends`` (S,) one past each segment's last non-zero
    byte — where the kernel's walk stops; ``tag_starts`` the bitmap's
    set bits, the events the kernel walks per state-word block.
    """
    nseg, length = data.shape
    chunk = rows * ROW_BYTES
    n_chunks = -(-length // chunk)
    npad = n_chunks * chunk
    if npad != length:
        data = jnp.pad(data, ((0, 0), (0, npad - length)))
    pos = jax.lax.broadcasted_iota(jnp.int32, data.shape, 1)
    ends = jnp.max(jnp.where(data != 0, pos + 1, 0), axis=1)
    # the lookahead tile is padded on as int32 words: padding the uint8
    # rows before the bitcast costs the TPU compiler a minute at one
    # 10 MB row
    words = jax.lax.bitcast_convert_type(
        data.reshape(nseg, npad // 4, 4), jnp.int32)
    words = jnp.pad(words, ((0, 0), (0, TILE_ROWS * LANES)))
    with jax.named_scope("tag_bitmap"):
        bm = tag_bitmap(data)
        tag_starts = jnp.sum(jax.lax.population_count(bm))
        bm = bm.reshape(nseg, n_chunks, -1, LANES)
        bm = jnp.pad(bm, ((0, 0), (0, 0),
                          (0, _bitmap_rows(rows) - bm.shape[2]), (0, 0)))
    return (words.reshape(nseg, -1, LANES),
            bm.reshape(nseg, -1, LANES), ends.astype(jnp.int32), tag_starts)


def _byte_specs() -> list:
    """Specs of the byte words and the tag-start bitmap: both stay in
    HBM, and :func:`_bytes_stream` copies them chunk by chunk."""
    return [pl.BlockSpec(memory_space=pl.ANY)] * 2


def _bytes_scratch(max_depth: int, n_docs: int, qr: int,
                   rows: int) -> list:
    """The bytes kernels' scratch: none of it grows with the row."""
    return [
        pltpu.VMEM((max_depth + 2, LANES), jnp.int32),   # tag stack
        pltpu.VMEM((n_docs, qr, LANES), jnp.int32),      # matched buf
        pltpu.VMEM((n_docs, qr, LANES), jnp.int32),      # first buf
        # two slots of one byte chunk + its lookahead tile, int32 words
        pltpu.SMEM((2, rows + TILE_ROWS, LANES), jnp.int32),
        # two slots of the chunk's tag-start bitmap words
        pltpu.SMEM((2, _bitmap_rows(rows), LANES), jnp.int32),
        # one DMA semaphore per slot: bytes, then bitmap
        pltpu.SemaphoreType.DMA((4,)),
    ]


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "chunk", "interpret",
                                    "grid_order"))
def stream_filter_bytes_pallas(data: jax.Array, starts: jax.Array,
                               tagmask: jax.Array, pw: jax.Array,
                               pb: jax.Array, selfloop_words: jax.Array,
                               init_words: jax.Array, acc_word: jax.Array,
                               acc_bit: jax.Array, *, max_depth: int,
                               chunk: int = 256,
                               interpret: bool | None = None,
                               grid_order: str = "bg"
                               ) -> tuple[jax.Array, jax.Array]:
    """One-launch raw bytes → per-document verdicts.

    data (S, L) uint8 packed segments; starts (S, D+1) int32 document
    start offsets per segment, INT32_MAX-filled past the last real
    document (see ``repro.core.events.SegmentPack``) — an unpacked batch
    is the degenerate D=1 with ``starts = [[0, INT32_MAX]] * B``.  Block
    tables as for :func:`stream_filter_pallas`.  ``chunk`` is *bytes*
    per SMEM chunk here (the event kernel's chunk counts events), rounded
    up to whole tiles of ``8 × 512`` bytes.  Returns matched/first
    (S, G, D, QB) int32 accept-lane outputs; the caller scatters
    document rows back to batch order.
    """
    from . import interpret_default

    if interpret is None:
        interpret = interpret_default()
    nseg = data.shape[0]
    n_docs = starts.shape[1] - 1
    tabs, dims = _kernel_tables(tagmask, pw, pb, selfloop_words,
                                init_words, acc_word, acc_bit)
    rows = _chunk_rows(chunk, ROW_BYTES)
    words, bm, ends, _ = _byte_rows(data, rows)
    g, qr = dims["g"], dims["qr"]
    grid, doc_axis, by_block, by_doc = _grid_maps(grid_order, nseg, g)
    out_spec = pl.BlockSpec(
        (None, None, n_docs, qr, LANES),
        lambda *ids: by_doc(*ids) + by_block(*ids) + (0, 0, 0))
    matched, first = pl.pallas_call(
        functools.partial(_bytes_kernel, max_depth=max_depth,
                          n_tags=dims["n_tags"], n_docs=n_docs,
                          doc_axis=doc_axis),
        grid=grid,
        in_specs=[_smem_full(), _smem_full(), *_byte_specs()]
        + _table_specs(by_block, dims),
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((nseg, g, n_docs, qr, LANES),
                                        jnp.int32)] * 2,
        scratch_shapes=_bytes_scratch(max_depth, n_docs, qr, rows),
        interpret=interpret,
        name="stream_filter_bytes_pallas",
    )(ends, starts.reshape(-1).astype(jnp.int32), words, bm, *tabs)
    return _lane_out(matched, dims["qb"]), _lane_out(first, dims["qb"])


@functools.partial(jax.jit,
                   static_argnames=("cap", "max_depth", "chunk",
                                    "interpret", "grid_order"))
def stream_filter_bytes_pallas_sparse(data: jax.Array, starts: jax.Array,
                                      doc_map: jax.Array,
                                      tagmask: jax.Array, pw: jax.Array,
                                      pb: jax.Array,
                                      selfloop_words: jax.Array,
                                      init_words: jax.Array,
                                      acc_word: jax.Array,
                                      acc_bit: jax.Array,
                                      lane_cls: jax.Array, *, cap: int,
                                      max_depth: int, chunk: int = 256,
                                      interpret: bool | None = None,
                                      grid_order: str = "bg"
                                      ) -> tuple[jax.Array, jax.Array]:
    """One launch raw bytes → bounded match list.

    The full fused datapath of :func:`stream_filter_bytes_pallas` plus
    the in-kernel sparse epilogue of :func:`stream_filter_pallas_sparse`:
    the ``(S, G, D, QB)`` accept bitmap never exists anywhere —
    per-document accept lanes are appended in VMEM to one shared bounded
    buffer of ``(doc_id, accept_class, first_event)`` entries.
    ``doc_map`` (S, D) int32 names each segment slot's global batch row
    (``SegmentPack.doc_ids``; ``-1`` = unused slot, dropped);
    ``lane_cls`` (G, QB) int32 accept-class names.  Returns
    ``(buf, count)`` with the same validity/overflow contract as the
    event-stream sparse wrapper, except that ``count`` is ``(1, 3)``:
    the match count, the tag starts the scalar walk visited per
    state-word block (the set bits of :func:`tag_bitmap`), and the
    chunks the walks fetched from HBM, summed over every (segment,
    state-word block) program.
    """
    from . import interpret_default

    if interpret is None:
        interpret = interpret_default()
    nseg = data.shape[0]
    n_docs = starts.shape[1] - 1
    tabs, dims = _kernel_tables(tagmask, pw, pb, selfloop_words,
                                init_words, acc_word, acc_bit, lane_cls)
    rows = _chunk_rows(chunk, ROW_BYTES)
    words, bm, ends, tag_starts = _byte_rows(data, rows)
    g, qr = dims["g"], dims["qr"]
    brows = _buffer_rows(cap)
    grid, doc_axis, by_block, _ = _grid_maps(grid_order, nseg, g)
    out, cnt = pl.pallas_call(
        functools.partial(_bytes_kernel_sparse, max_depth=max_depth,
                          n_tags=dims["n_tags"], n_docs=n_docs,
                          doc_axis=doc_axis, cap=int(cap)),
        grid=grid,
        in_specs=[_smem_full(), _smem_full(), _smem_full(),
                  *_byte_specs()]
        + _table_specs(by_block, dims, with_cls=True),
        out_specs=[
            pl.BlockSpec((3, brows, LANES), lambda *ids: (0, 0, 0)),
            pl.BlockSpec((1, 2), lambda *ids: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((3, brows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
        ],
        scratch_shapes=_bytes_scratch(max_depth, n_docs, qr, rows) + [
            pltpu.VMEM((2 * qr, LANES), jnp.int32),
            pltpu.SMEM((2 * qr, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="stream_filter_bytes_pallas_sparse",
    )(ends, starts.reshape(-1).astype(jnp.int32),
      doc_map.reshape(-1).astype(jnp.int32), words, bm, *tabs)
    return (_match_list(out, int(cap)),
            jnp.stack([cnt[0, 0], tag_starts.astype(jnp.int32),
                       cnt[0, 1]]).reshape(1, 3))
