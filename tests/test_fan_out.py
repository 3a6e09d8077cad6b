"""FilterStage._fan_out: one pass over a batch's match list.

Every case compares the stage's fan-out with the per-document loop it
replaced (:func:`_reference_fan_out`, kept here as the reference): the
same routed documents in the same order, the same ids, element for
element and of the same dtype.
"""
import numpy as np
import pytest

from repro.core.dictionary import TagDictionary
from repro.core.engines.result import FilterResult, SparseResult
from repro.data.filter_stage import FilterStage, RoutedDocument
from repro.data.generator import DTD, gen_profiles

N_QUERIES = 24


def _stage(n_shards: int, keep_unmatched: bool) -> FilterStage:
    dtd = DTD.generate(n_tags=16, seed=3)
    d = TagDictionary()
    dtd.register(d)
    profiles = gen_profiles(dtd, n=N_QUERIES, length=3, seed=3)
    return FilterStage(profiles, d, n_shards=n_shards, engine="levelwise",
                       keep_unmatched=keep_unmatched)


def _reference_fan_out(stage, results, nbytes, base=0, *, gids=None,
                       seqs=None) -> list[RoutedDocument]:
    """The per-document fan-out: one full-list mask per document."""
    sparse = isinstance(results, SparseResult)
    live = stage._gids if gids is None else gids
    out = []
    for i, nb in enumerate(nbytes):
        doc = base + i if seqs is None else int(seqs[i])
        if sparse:
            qids = results.matching_queries(i)
            if results.live_ids is None:
                qids = live[qids]
        else:
            qids = live[results[i].matching_queries()]
        if len(qids) == 0:
            if stage.keep_unmatched:
                out.append(RoutedDocument(doc, qids, 0, nb))
            continue
        for shard in np.unique(stage.shard_of_profile[qids]):
            mine = qids[stage.shard_of_profile[qids] == shard]
            out.append(RoutedDocument(doc, mine, int(shard), nb))
    return out


def _dense(batch: int, empty=(), seed=0) -> FilterResult:
    """A (batch, N_QUERIES) verdict with the rows in ``empty`` unmatched."""
    rng = np.random.default_rng(seed)
    matched = rng.random((batch, N_QUERIES)) < 0.4
    matched[list(empty)] = False
    first = np.where(matched, rng.integers(0, 500, matched.shape),
                     np.iinfo(np.int32).max)
    return FilterResult(matched, first)


def _perm(seed=1) -> np.ndarray:
    return np.random.default_rng(seed).permutation(N_QUERIES).astype(np.int32)


def _overflowed() -> SparseResult:
    sp = _dense(6, empty=(2,)).sparsify(np.arange(N_QUERIES, dtype=np.int32))
    sp.overflowed = True  # as the engines' dense fallback marks it
    return sp


# name → (result, n documents, fan-out kwargs, resorts expected)
CASES = {
    "live_ids-none": lambda: (_dense(8).sparsify(), 8, {}, 0),
    "live_ids-arange": lambda: (
        _dense(8).sparsify(np.arange(N_QUERIES, dtype=np.int32)), 8, {}, 0),
    "live_ids-permuted": lambda: (_dense(8).sparsify(_perm()), 8, {}, 1),
    "gids-permuted": lambda: (_dense(8).sparsify(), 8,
                              {"gids": _perm(2)}, 0),
    "unmatched-start-middle-end": lambda: (
        _dense(9, empty=(0, 4, 8)).sparsify(), 9, {}, 0),
    "all-unmatched": lambda: (_dense(5, empty=range(5)).sparsify(), 5,
                              {}, 0),
    "batch-of-one": lambda: (_dense(1).sparsify(), 1, {}, 0),
    "seqs-non-contiguous": lambda: (
        _dense(5, empty=(1,)).sparsify(), 5,
        {"seqs": [3, 7, 8, 20, 41]}, 0),
    "base-offset": lambda: (_dense(4).sparsify(), 4, {"base": 12}, 0),
    "pad-rows": lambda: (_dense(8).sparsify(), 5, {}, 0),
    "overflowed": lambda: (_overflowed(), 6, {}, 0),
    "dense": lambda: (_dense(7, empty=(3,)), 7, {}, 0),
    "dense-gids-permuted": lambda: (_dense(7, empty=(0,)), 7,
                                    {"gids": _perm(4)}, 0),
}


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g.doc_index, g.shard, g.nbytes) == \
            (w.doc_index, w.shard, w.nbytes)
        assert type(g.shard) is int
        assert g.matched_profiles.dtype == w.matched_profiles.dtype
        np.testing.assert_array_equal(g.matched_profiles, w.matched_profiles)


@pytest.mark.parametrize("keep_unmatched", [True, False])
@pytest.mark.parametrize("n_shards", [1, 3])
@pytest.mark.parametrize("case", sorted(CASES))
def test_fan_out_matches_per_document_loop(case, n_shards, keep_unmatched):
    stage = _stage(n_shards, keep_unmatched)
    res, n_docs, kw, resorts = CASES[case]()
    nbytes = [100 + 10 * i for i in range(n_docs)]
    want = _reference_fan_out(stage, res, nbytes, **kw)
    got = stage._fan_out(res, nbytes, **kw)
    _assert_same(got, want)
    assert stage.stats["fan_out_resorted"] == resorts
    if n_shards == 1:  # one routed document per matched document
        assert len({r.doc_index for r in got}) == len(got)


def test_fan_out_never_masks_per_document(monkeypatch):
    stage = _stage(3, True)
    res = _dense(16, empty=(5, 15)).sparsify()
    nbytes = [64] * 16
    want = _reference_fan_out(stage, res, nbytes)

    def per_document(self, doc):
        raise AssertionError("fan-out masked the match list per document")

    monkeypatch.setattr(SparseResult, "matching_queries", per_document)
    _assert_same(stage._fan_out(res, nbytes), want)


@pytest.mark.parametrize("live_ids", [None, "permuted"])
def test_rows_by_document_slices_match_matching_queries(live_ids):
    res = _dense(10, empty=(0, 9)).sparsify(
        None if live_ids is None else _perm(5))
    ids, bounds, resorted = res.rows_by_document()
    assert resorted == (live_ids is not None)
    assert bounds.shape == (11,)
    for i in range(10):
        np.testing.assert_array_equal(ids[bounds[i]:bounds[i + 1]],
                                      res.matching_queries(i))
