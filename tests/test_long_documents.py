"""Whole long documents through the served stage, on the CPU.

The bytes megakernel leaves each row in HBM and copies it to SMEM one
4,096-byte chunk at a time, the next chunk's copy in flight while the
current one is walked.  These tests drive seeded XMark sites from the
benchmark's generator through ``FilterStage(engine="streaming",
sparse=True)`` with the kernel in the Pallas interpreter: a document of
dozens of chunks, a tag cut by a chunk edge, a document that ends on a
chunk edge, and a batch of one long and one short document.  Each
compares the match lists with the benchmark's plain reference and every
``(document, profile, first event)`` row with the ``oracle`` engine,
exactly.  A kernel planted with a stack reset at every chunk, or with a
walk that stops short of the row's end, fails.
"""
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, planted  # noqa: E402
from bench.traffic import generator  # noqa: E402
from repro.core import engines  # noqa: E402
from repro.core.dictionary import TagDictionary  # noqa: E402
from repro.core.engines import streaming  # noqa: E402
from repro.core.events import EventBatch, EventStream  # noqa: E402
from repro.data.filter_stage import FilterStage  # noqa: E402
from repro.kernels import stream_filter as sf  # noqa: E402

#: wire bytes per chunk of the walk (``DEFAULT_BYTE_CHUNK`` rounded up
#: to whole (8, 128) tiles of int32 words)
CHUNK = sf._chunk_rows(streaming.DEFAULT_BYTE_CHUNK, sf.ROW_BYTES) \
    * sf.ROW_BYTES
#: xmark-1k's deployment at larger and smaller scales: sites of 46-50
#: chunks, and one of 3
SCALE = 0.002
SHORT_SCALE = 0.0001


@pytest.fixture(scope="module")
def xmark():
    cfg = harness.load_json(ROOT / "bench" / "configs" / "xmark-1k.json")
    dep = generator.deployment(dict(cfg, scale_factor=SCALE))
    docs = dict(cfg["documents"], pool=2)
    long_pool = generator.Pool.build(dep, docs, seed=2)
    short = generator.Pool.build(
        generator.Deployment(dep.dtd, dep.profiles, SHORT_SCALE),
        dict(docs, pool=1), seed=3)
    d = TagDictionary.build(dep.names)
    ref = harness.load_module(ROOT / "bench" / "configs" / "linear_xpath.py")
    return dep, long_pool, short, d, ref


def _stage(xmark, bucket: int, batch: int) -> FilterStage:
    dep, _, _, d, _ = xmark
    n = len(set(dep.profiles))
    return FilterStage(dep.profiles, d, engine="streaming", sparse=True,
                       keep_unmatched=True, batch_size=batch,
                       byte_bucket=bucket,
                       engine_options={"kernel": "pallas",
                                       "match_cap": batch * n})


def _tag_starts(buf: bytes) -> np.ndarray:
    return np.flatnonzero(np.frombuffer(buf, np.uint8) == ord("<"))


def _insert_text(pool, i: int, at: int, n: int) -> bytes:
    """Tree ``i``'s payload with ``n`` text bytes added after the last
    text byte before offset ``at``: the structure stays the tree's and
    every tag from ``at`` on moves ``n`` bytes on."""
    buf = pool.templates[i]
    pos = pool.text_pos[i]
    t = int(pos[pos < at].max()) + 1
    return bytes(buf[:t]) + b"x" * n + bytes(buf[t:])


def _check(xmark, stage, bufs, trees):
    """Match lists against the reference, first events against the
    oracle engine; returns the stage's sparse result."""
    dep, _, _, d, ref = xmark
    res = stage._filter_bytebatch(bufs)
    assert res.meta["path"] == "kernel-fused"
    matcher = ref.Reference(dep.profiles, dep.names)
    streams = []
    for k, (buf, (kinds, tags)) in enumerate(zip(bufs, trees)):
        k_dec, t_dec = ref.decode(buf)
        np.testing.assert_array_equal(k_dec, kinds)
        np.testing.assert_array_equal(t_dec, tags)
        np.testing.assert_array_equal(res.matching_queries(k),
                                      matcher.match(kinds, tags))
        streams.append(EventStream(kinds, tags))
    oracle = engines.create("oracle", stage.nfa, dictionary=d)
    want = oracle.filter_batch(EventBatch.from_streams(streams))
    got = res.densify()
    np.testing.assert_array_equal(got.matched, want.matched)
    np.testing.assert_array_equal(
        np.where(got.matched, got.first_event, -1),
        np.where(want.matched, want.first_event, -1))
    return res


def _chunks_walked(bufs) -> int:
    return sum(-(-len(b.rstrip(b"\0")) // CHUNK) for b in bufs)


def test_a_document_of_forty_chunks(xmark):
    _, pool, _, _, _ = xmark
    buf = pool.templates[0].tobytes()
    assert len(buf) >= 40 * CHUNK
    stage = _stage(xmark, -(-len(buf) // CHUNK) * CHUNK, 1)
    res = _check(xmark, stage, [buf], [(pool.kinds[0], pool.tags[0])])
    assert res.n_matches > 0
    # one plan block: the walk fetched every chunk of the row once
    assert stage._eng.plan_.meta["n_blocks"] == 1
    assert stage.stats["stream_chunks"] == _chunks_walked([buf])
    assert stage.stats["tag_starts"] == len(pool.kinds[0])


@pytest.mark.parametrize("cut", [1, 2, 3, 4])
def test_a_tag_cut_by_a_chunk_edge(xmark, cut):
    """A tag whose first ``cut`` bytes end one chunk and whose rest
    begins the next: its pre-decode reads the lookahead tile."""
    _, pool, _, _, _ = xmark
    edge = 20 * CHUNK
    starts = _tag_starts(pool.templates[1].tobytes())
    p = int(starts[starts > edge - cut][0])
    buf = _insert_text(pool, 1, p, edge - cut - p + CHUNK)
    assert (_tag_starts(buf) == edge - cut + CHUNK).any()
    stage = _stage(xmark, -(-len(buf) // CHUNK) * CHUNK, 1)
    _check(xmark, stage, [buf], [(pool.kinds[1], pool.tags[1])])


def test_a_document_that_ends_on_a_chunk_edge(xmark):
    """The row is the document: its last close tag ends the last chunk,
    and nothing but the zero lookahead tile follows it."""
    _, pool, _, _, _ = xmark
    n = len(pool.templates[0])
    buf = _insert_text(pool, 0, n, -n % CHUNK)
    assert len(buf) % CHUNK == 0 and buf.endswith(b">")
    stage = _stage(xmark, len(buf), 1)
    _check(xmark, stage, [buf], [(pool.kinds[0], pool.tags[0])])
    assert stage.stats["stream_chunks"] == len(buf) // CHUNK


def test_a_batch_of_one_long_and_one_short_document(xmark):
    _, pool, short, _, _ = xmark
    bufs = [pool.templates[1].tobytes(), short.templates[0].tobytes()]
    assert len(bufs[1]) < 4 * CHUNK and len(bufs[0]) >= 40 * CHUNK
    stage = _stage(xmark, -(-len(bufs[0]) // CHUNK) * CHUNK, 2)
    res = _check(xmark, stage, bufs, [(pool.kinds[1], pool.tags[1]),
                                      (short.kinds[0], short.tags[0])])
    assert set(np.unique(res.doc_ids)) == {0, 1}
    assert stage.stats["stream_chunks"] == _chunks_walked(bufs)


@pytest.mark.parametrize("fault", sorted(planted.FAULTS))
def test_a_planted_walk_fault_is_caught(xmark, monkeypatch, fault):
    """The benchmark's planted kernels (``bench/planted.py``): a walk
    that stops at nine tenths of its row, a stack emptied at every
    chunk."""
    _, pool, _, _, _ = xmark
    monkeypatch.setattr(streaming, "sf", planted.planted_kernels(fault))
    # a copy of the launch function: jit keys its traces by the function,
    # so the original's traces neither serve nor see the planted kernel
    fn = streaming._run_bytes_fused_sparse.__wrapped__
    copy = types.FunctionType(fn.__code__, fn.__globals__, fn.__name__,
                              fn.__defaults__, fn.__closure__)
    copy.__kwdefaults__ = fn.__kwdefaults__
    monkeypatch.setattr(streaming, "_run_bytes_fused_sparse", jax.jit(
        copy, static_argnames=("cap", "interpret")))
    buf = pool.templates[0].tobytes()
    stage = _stage(xmark, -(-len(buf) // CHUNK) * CHUNK, 1)
    with pytest.raises(AssertionError, match="Arrays are not equal"):
        _check(xmark, stage, [buf], [(pool.kinds[0], pool.tags[0])])
