"""Bit-equivalence of the streaming megakernel against the scan oracle.

The Pallas megakernel (``StreamingEngine(kernel="pallas")``) must be
*bit-identical* to the ``lax.scan`` path (``kernel="scan"``) on every
plan and every batch — ragged/padded batches, churned (add/remove-query)
sharded plans, depth-overflow documents, fused byte ingestion and the
2-D mesh program.  Tests run the kernels in interpret mode; the compiled
mode is covered by ``tests/test_chip_compile.py`` (Mosaic compiles the
same kernels for a described TPU) and by ``chip_smoke.py`` on the chip.
"""

import numpy as np
import pytest

from repro.core import engines
from repro.core.dictionary import TagDictionary
from repro.core.engines.base import FilterEngine
from repro.core.engines.streaming import DEFAULT_BYTE_CHUNK
from repro.core.events import (CLOSE, OPEN, SEG_SENTINEL, ByteBatch,
                               EventBatch, EventStream, _sym_table,
                               decode_tags, encode_bytes, pack_segments)
from repro.core.nfa import compile_queries
from repro.data.generator import DTD, gen_corpus, gen_profiles
from repro.kernels import stream_filter as sf

#: the Pallas interpreter runs on any backend
MODES = [pytest.param(True, id="interpret")]


def workload(n_queries=32, seed=0, n_tags=14, p_wild=0.1, p_desc=0.3,
             length=4):
    dtd = DTD.generate(n_tags=n_tags, seed=seed)
    d = TagDictionary()
    dtd.register(d)
    qs = gen_profiles(dtd, n=n_queries, length=length, p_wild=p_wild,
                      p_desc=p_desc, seed=seed)
    return dtd, d, qs, compile_queries(qs, d, shared=True)


def engine_pair(nfa, d, interpret, **kw):
    """The scan oracle and the megakernel over the SAME profile set."""
    scan = engines.create("streaming", nfa, dictionary=d,
                          kernel="scan", **kw)
    pallas = engines.create("streaming", nfa, dictionary=d,
                            kernel="pallas", kernel_interpret=interpret,
                            **kw)
    return scan, pallas


def assert_same(a, b):
    np.testing.assert_array_equal(a.matched, b.matched)
    np.testing.assert_array_equal(a.first_event, b.first_event)


# ------------------------------------------------------------ batch paths
class TestKernelVsScanBatches:
    @pytest.mark.parametrize("interpret", MODES)
    @pytest.mark.parametrize("n_queries,seed", [(8, 0), (40, 1), (64, 2)])
    def test_ragged_padded_batches(self, interpret, n_queries, seed):
        """Documents of wildly different lengths in one bucketed batch:
        the PAD tail must be inert on both paths."""
        dtd, d, qs, nfa = workload(n_queries=n_queries, seed=seed)
        docs = [ev for n in (4, 30, 90) for ev in
                gen_corpus(dtd, n_docs=2, nodes_per_doc=n, seed=seed + n)]
        batch = EventBatch.from_streams(docs, bucket=64)
        scan, pallas = engine_pair(nfa, d, interpret)
        assert_same(scan.filter_batch(batch), pallas.filter_batch(batch))

    @pytest.mark.parametrize("interpret", MODES)
    def test_multi_block_plan(self, interpret):
        """Small blk forces several word-blocks per document."""
        dtd, d, qs, nfa = workload(n_queries=48, seed=3, p_desc=0.5)
        docs = gen_corpus(dtd, n_docs=4, nodes_per_doc=70, seed=3)
        batch = EventBatch.from_streams(docs, bucket=64)
        scan, pallas = engine_pair(nfa, d, interpret, blk=32, chunk=32)
        plan = pallas.plan_
        assert plan.meta["n_blocks"] > 1
        assert_same(scan.filter_batch(batch), pallas.filter_batch(batch))

    @pytest.mark.parametrize("interpret", MODES)
    def test_fused_bytes_path(self, interpret):
        """Raw wire bytes → verdict, parse+kernel in one program."""
        dtd, d, qs, nfa = workload(n_queries=24, seed=4)
        docs = gen_corpus(dtd, n_docs=5, nodes_per_doc=50, seed=4)
        bb = ByteBatch.from_streams(docs, text_fill=3, bucket=256)
        scan, pallas = engine_pair(nfa, d, interpret)
        assert_same(scan.filter_bytes(bb), pallas.filter_bytes(bb))


# ------------------------------------------------ the tag-start bitmap walk
#: bytes per SMEM chunk of the bytes kernel at the default ``byte_chunk``
CHUNK = sf._chunk_rows(DEFAULT_BYTE_CHUNK, sf.ROW_BYTES) * sf.ROW_BYTES


def _laid_out(ev, at):
    """Wire bytes of ``ev`` with event ``i``'s ``<`` at byte ``at(i)``,
    ``x`` filler in between (``at`` must leave room for each tag)."""
    out = bytearray()
    for i, (k, t) in enumerate(zip(ev.kind, ev.tag_id)):
        out += b"x" * (at(i) - len(out))
        assert len(out) == at(i)
        sym = TagDictionary.symbols_of(int(t)).encode()
        out += (b"<" + sym + b">") if k == OPEN else (b"</" + sym + b">")
    return bytes(out)


def _edge_case(case, dtd):
    """(payloads, pack) of one edge case of the bitmap walk."""
    docs = gen_corpus(dtd, n_docs=3, nodes_per_doc=90, seed=21)
    if case == "word-straddle":      # every tag crosses a 32-byte word
        return [_laid_out(ev, lambda i: 32 * i + 30) for ev in docs], False
    if case == "chunk-straddle":     # tags at CHUNK-2.. and CHUNK-3..
        ev = docs[0]
        assert len(ev.kind) > CHUNK // 32
        return [_laid_out(ev, lambda i: 32 * i + 30),
                _laid_out(ev, lambda i: 32 * i + 29)], False
    if case == "bit-31":             # every word's one bit is its sign
        return [_laid_out(ev, lambda i: 32 * i + 31) for ev in docs], False
    if case == "lt-last":            # '<', '</', '<a' as the last bytes
        body = encode_bytes(docs[0], text_fill=3)
        return [body + b"<", body + b"</", body + b"<a",
                b"x" * 31 + b"<"], False
    if case == "invalid-markers":    # '<' / '</' before non-symbol bytes
        junk = [b"<!a>", b"</ a>", b"<a!>", b"<<", b"</<", b"< ", b"<a>"]
        bufs = []
        for j, ev in enumerate(docs):
            good = encode_bytes(ev, text_fill=2).split(b">")
            bufs.append(b">".join(
                g + junk[(i + j) % len(junk)] for i, g in enumerate(good)))
        return bufs, False
    if case == "open-last":          # the last tag opens, in a part word
        ev, bufs = docs[0], []
        for j in np.flatnonzero(docs[0].kind == OPEN):
            body = encode_bytes(EventStream(ev.kind[:j + 1],
                                            ev.tag_id[:j + 1]))
            bufs.append(b"x" * ((10 - len(body)) % 32) + body)
        return bufs, False
    if case == "tag-dense":          # no text: one tag per 4-5 bytes
        return [encode_bytes(ev) for ev in docs], False
    if case == "packed":             # boundaries inside words, empty docs
        small = gen_corpus(dtd, n_docs=6, nodes_per_doc=7, seed=22)
        bufs = [encode_bytes(ev, text_fill=1 + i % 3)
                for i, ev in enumerate(small)]
        return bufs[:2] + [b""] + bufs[2:] + [b"", b""], True
    raise ValueError(case)


class TestTagStartWalk:
    """The bytes kernels' scalar walk visits only the tag-start bitmap's
    set bits; on bytes chosen to stress the bitmap's edges, both launch
    shapes stay bit-identical to the scan, and the counter of walked tag
    starts equals the host decoder's event count."""

    @pytest.mark.parametrize("interpret", MODES)
    @pytest.mark.parametrize("case", [
        "word-straddle", "chunk-straddle", "bit-31", "lt-last",
        "open-last", "invalid-markers", "tag-dense", "packed"])
    def test_bytes_kernels_match_scan(self, interpret, case):
        dtd, d, qs, nfa = workload(n_queries=24, seed=6)
        bufs, pack = _edge_case(case, dtd)
        bb = ByteBatch.from_buffers(bufs)
        scan, pallas = engine_pair(nfa, d, interpret, segment_target=512)
        if pack:
            starts = pack_segments(bb, target_len=512).starts
            real = starts[(starts > 0) & (starts < SEG_SENTINEL)]
            assert (real % 32 != 0).any()
            assert any(len(b) == 0 for b in bufs)
        oracle = scan.filter_bytes(bb)
        assert oracle.matched.any()
        if case == "open-last":      # some document first matches there
            last = [len(decode_tags(b, _sym_table())[0]) - 1 for b in bufs]
            assert (oracle.first_event == np.array(last)[:, None]).any()
        assert_same(oracle, pallas.filter_bytes(bb, pack=pack))
        sp = pallas.filter_bytes_sparse(bb, pack=pack)
        assert sp.meta["path"] == "kernel-fused"
        assert_same(oracle, sp.densify())
        assert sp.meta["tag_starts"] == sum(
            len(decode_tags(b, _sym_table())[0]) for b in bufs)


def _bitmap_offsets(words: np.ndarray) -> list[np.ndarray]:
    """(S, W) int32 bitmap words → the set bit offsets of each row."""
    bits = (words.view(np.uint32)[..., None]
            >> np.arange(32, dtype=np.uint32)) & 1
    return [np.flatnonzero(r) for r in bits.reshape(words.shape[0], -1)]


class TestTagBitmap:
    """:func:`repro.kernels.stream_filter.tag_bitmap` sets exactly the
    offsets where the host decoder starts an event: bit order, rows
    padded past their bytes, and the lookahead at the row's end."""

    def _check(self, bufs, bucket=None):
        bb = ByteBatch.from_buffers(bufs, bucket=bucket)
        words = np.asarray(sf.tag_bitmap(np.asarray(bb.data)))
        assert words.shape[1] == -(-bb.data.shape[1] // 32)
        got = _bitmap_offsets(words)
        for buf, offs in zip(bufs, got):
            np.testing.assert_array_equal(
                offs, decode_tags(buf, _sym_table())[0])

    def test_random_rows(self):
        rng = np.random.default_rng(0)
        alphabet = np.frombuffer(b"<<<//ab_.Z9x >\x00", np.uint8)
        self._check([rng.choice(alphabet, size=n).tobytes()
                     for n in (1, 31, 32, 33, 100, 257, 1000)], bucket=64)

    def test_generated_rows(self):
        dtd, d, _, _ = workload(n_queries=4, seed=13)
        docs = gen_corpus(dtd, n_docs=4, nodes_per_doc=60, seed=13)
        self._check([encode_bytes(ev, text_fill=f)
                     for f, ev in enumerate(docs)], bucket=128)

    def test_row_end_lookahead(self):
        """Tags whose symbols are the row's last bytes, with no padding
        after them: an open tag needs two bytes after ``<``, a close
        tag three."""
        self._check([b"x" * 61 + b"<ab", b"x" * 60 + b"</ab",
                     b"x" * 61 + b"</a", b"x" * 62 + b"<a",
                     b"x" * 63 + b"<"])


# --------------------------------------------------------- depth overflow
class TestDepthOverflow:
    def _deep_doc(self, d, tag_name, depth):
        tid = d.lookup(tag_name)
        kind = np.array([OPEN] * depth + [CLOSE] * depth, np.int8)
        return EventStream(kind, np.full(2 * depth, tid, np.int32))

    @pytest.mark.parametrize("interpret", MODES)
    @pytest.mark.parametrize("depth", [5, 6, 7, 12])
    def test_deeper_than_max_depth(self, interpret, depth):
        """Documents at/over the stack bound clip identically on both
        paths (host-built batches skip the parse-time depth check)."""
        dtd, d, qs, nfa = workload(n_queries=16, seed=5, p_wild=0.0)
        tag = next(st.tag for q in qs for st in q.steps if st.tag != "*")
        docs = [self._deep_doc(d, tag, depth)] \
            + gen_corpus(dtd, n_docs=2, nodes_per_doc=30, seed=5)
        batch = EventBatch.from_streams(docs, bucket=32)
        scan, pallas = engine_pair(nfa, d, interpret, max_depth=6)
        assert scan.plan_.meta["max_depth"] == 6
        assert pallas.plan_.meta["max_depth"] == 6
        assert_same(scan.filter_batch(batch), pallas.filter_batch(batch))


# ----------------------------------------------------------- churned plans
class TestChurnedPlans:
    @pytest.mark.parametrize("interpret", MODES)
    @pytest.mark.parametrize("n_parts", [1, 2])
    def test_add_remove_queries(self, interpret, n_parts):
        """Sharded plans stay bit-identical through subscribe (one-part
        recompile, incremental restack) and unsubscribe (tombstones)."""
        dtd, d, qs, nfa = workload(n_queries=20, seed=6)
        docs = gen_corpus(dtd, n_docs=4, nodes_per_doc=50, seed=6)
        batch = EventBatch.from_streams(docs, bucket=64)
        scan, pallas = engine_pair(nfa, d, interpret)
        sp_s = scan.plan_sharded(n_parts)
        sp_p = pallas.plan_sharded(n_parts)
        extra = gen_profiles(dtd, n=4, length=3, seed=77)
        gids_s: list[int] = []
        for q in extra:  # one op at a time: exercises the restack path
            sp_s, g1 = sp_s.add_queries([q])
            sp_p, g2 = sp_p.add_queries([q])
            assert g1 == g2
            gids_s += g1
        sp_s = sp_s.remove_queries([1, gids_s[0]])
        sp_p = sp_p.remove_queries([1, gids_s[0]])
        assert_same(scan.filter_batch_sharded(batch, sp_s),
                    pallas.filter_batch_sharded(batch, sp_p))

    @pytest.mark.parametrize("interpret", MODES)
    def test_sharded_bytes_2d(self, interpret):
        """The 2-D (data × model) bytes→verdict program through the
        kernel equals the scan program on the same mesh."""
        from repro.launch.mesh import make_filter_mesh

        dtd, d, qs, nfa = workload(n_queries=16, seed=7)
        docs = gen_corpus(dtd, n_docs=5, nodes_per_doc=40, seed=7)
        bb = ByteBatch.from_streams(docs, text_fill=2, bucket=256)
        scan, pallas = engine_pair(nfa, d, interpret)
        mesh = make_filter_mesh(2)
        assert_same(
            scan.filter_bytes_sharded2d(bb, scan.plan_sharded(2),
                                        mesh=mesh),
            pallas.filter_bytes_sharded2d(bb, pallas.plan_sharded(2),
                                          mesh=mesh))


# ------------------------------------------------- selection and autotune
class TestKernelSelection:
    def test_auto_prefers_scan_under_interpret(self, monkeypatch):
        """kernel="auto" = megakernel exactly when Pallas compiles (a
        real TPU); the interpreter is a correctness tool, not a path.
        The choice is frozen when the engine is constructed."""
        _, d, qs, nfa = workload(n_queries=8, seed=8)
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        eng = engines.create("streaming", nfa, dictionary=d)
        assert eng.kernel_mode == "auto" and not eng._kernel_on()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        eng = engines.create("streaming", nfa, dictionary=d)
        assert eng._kernel_on()

    def test_invalid_mode_rejected(self):
        _, d, qs, nfa = workload(n_queries=4, seed=9)
        with pytest.raises(ValueError, match="kernel="):
            engines.create("streaming", nfa, dictionary=d, kernel="maybe")

    def test_autotune_blocks_respects_budgets(self):
        cfg = FilterEngine.autotune_blocks(4096, 64, n_tags=64)
        assert cfg["blk"] % 32 == 0 and cfg["chunk"] >= 32
        # a tiny NFA never gets a block wider than its padded state count
        small = FilterEngine.autotune_blocks(40, 64, n_tags=64)
        assert small["blk"] == 64
        # a huge tag space shrinks the block until the masks fit VMEM
        tight = FilterEngine.autotune_blocks(
            4096, 64, n_tags=4096, vmem_budget=128 << 10)
        assert tight["blk"] == 128 < cfg["blk"]
        # SMEM budget caps the event chunk (double-buffered int32)
        assert FilterEngine.autotune_blocks(
            256, 64, n_tags=16, smem_budget=512)["chunk"] == 64

    def test_engine_options_override_autotune(self):
        _, d, qs, nfa = workload(n_queries=24, seed=10)
        eng = engines.create("streaming", nfa, dictionary=d,
                             kernel="pallas", blk=64, chunk=96)
        assert eng.plan_.meta["blk"] % 32 == 0
        assert eng.plan_.meta["blk"] >= 64
        assert eng.plan_.meta["chunk"] == 96

    def test_scan_plans_skip_kernel_tables(self):
        """Scan-only engines (the default off TPU) pay neither the block
        layout nor the kb_* table memory; megakernel engines carry both."""
        _, d, qs, nfa = workload(n_queries=12, seed=13)
        scan = engines.create("streaming", nfa, dictionary=d, kernel="scan")
        assert not any(k.startswith("kb_") for k in scan.plan_.tables)
        assert "blk" not in scan.plan_.meta
        pallas = engines.create("streaming", nfa, dictionary=d,
                                kernel="pallas")
        assert "kb_tagmask" in pallas.plan_.tables

    def test_layout_pad_overflow_raises_typed_error(self):
        from repro.core.nfa import pad_states
        from repro.kernels.blocks import PadOverflow, state_layout

        _, d, qs, nfa = workload(n_queries=24, seed=14)
        nfa = pad_states(nfa, 32)
        mk = state_layout(nfa, blk=32)
        with pytest.raises(PadOverflow):
            state_layout(nfa, blk=32, n_blocks=mk.n_blocks - 1)
        with pytest.raises(PadOverflow):
            state_layout(nfa, blk=32,
                         block_queries=mk.block_queries - 1)

    def test_churn_sequence_never_overflows(self):
        """Long add/remove sequence on a kernel-enabled sharded plan:
        bucket overflows must reconcile (merge_pads / PadOverflow
        fallback), never crash, and stay bit-identical to the scan."""
        dtd, d, qs, nfa = workload(n_queries=12, seed=15)
        docs = gen_corpus(dtd, n_docs=3, nodes_per_doc=40, seed=15)
        batch = EventBatch.from_streams(docs, bucket=64)
        scan, pallas = engine_pair(nfa, d, True)
        sp_s, sp_p = scan.plan_sharded(2), pallas.plan_sharded(2)
        extra = gen_profiles(dtd, n=24, length=5, p_desc=0.5, seed=99)
        gids: list[int] = []
        for i, q in enumerate(extra):
            sp_s, g = sp_s.add_queries([q])
            sp_p, _ = sp_p.add_queries([q])
            gids += g
            if i % 3 == 2:
                sp_s = sp_s.remove_queries([gids[i // 3]])
                sp_p = sp_p.remove_queries([gids[i // 3]])
        assert_same(scan.filter_batch_sharded(batch, sp_s),
                    pallas.filter_batch_sharded(batch, sp_p))

    def test_plan_meta_threads_one_max_depth(self):
        """Satellite: kernel and scan read the same stack bound — the
        plan metadata, never a per-path default."""
        _, d, qs, nfa = workload(n_queries=8, seed=11)
        eng = engines.create("streaming", nfa, dictionary=d, max_depth=17)
        assert eng.plan_.meta["max_depth"] == 17
        from repro.kernels.ops import StreamFilterKernelEngine
        from repro.kernels.parse import DEFAULT_MAX_DEPTH
        ke = StreamFilterKernelEngine(list(qs), d)
        assert ke.max_depth == DEFAULT_MAX_DEPTH
        assert ke._eng.plan_.meta["max_depth"] == DEFAULT_MAX_DEPTH


class TestEventBucketThreading:
    def test_stage_bucket_reaches_engine_byte_path(self):
        """Satellite: a FilterStage's bucket becomes the engine default
        for every byte path instead of a silent hard-coded 128."""
        from repro.data.filter_stage import FilterStage

        dtd, d, qs, nfa = workload(n_queries=6, seed=12)
        stage = FilterStage(profiles=list(qs), dictionary=d, n_shards=2,
                            engine="streaming", bucket=64)
        assert stage._eng._event_bucket(None) == 64
        assert stage._eng._event_bucket(32) == 32
        # engines built standalone keep the documented default
        eng = engines.create("streaming", nfa, dictionary=d)
        from repro.core.engines.base import DEFAULT_EVENT_BUCKET
        assert eng._event_bucket(None) == DEFAULT_EVENT_BUCKET
