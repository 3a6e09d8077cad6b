"""Fused in-kernel sparse epilogue: one launch from bytes to match lists.

PR-level contract, four legs:

* **Route taxonomy** — every sparse call records which path actually ran
  in ``SparseResult.meta["path"]``: ``kernel-fused`` (in-kernel bounded
  emission), ``lane-compact`` (two-launch bitmap compaction),
  ``base-fallback`` (non-kernel engines through the base class) and
  ``dense-overflow`` (buffer saturated, exact dense recompute).
* **Overflow boundaries** — matches == cap, cap ± 1, zero matches and
  all-docs-match-everything are each bit-exact against the scan oracle
  via ``densify()`` on the plain, sharded, bytes and churned-gid paths.
* **No bitmap in HBM** — a jaxpr inspection asserts the fused program's
  ``pallas_call`` outputs are ONLY the bounded field-major
  ``(3, rows, 128)`` match buffer and the ``(1, 1)`` counter: the ``(B, G, QB)`` accept bitmap
  never materializes outside VMEM.
* **Kernel vs oracle** — the raw kernel's buffer equals
  :func:`repro.kernels.ref.sparse_epilogue` row for row (emission order
  included) across grid orders and caps, saturation included.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engines
from repro.core.dictionary import TagDictionary
from repro.core.events import ByteBatch, EventBatch
from repro.core.nfa import compile_queries
from repro.core.xpath import parse
from repro.data.generator import DTD, gen_corpus, gen_profiles
from repro.kernels import ref
from repro.kernels import stream_filter as sf
from repro.launch.mesh import make_filter_mesh

KERNEL_OPTS = dict(kernel="pallas", kernel_interpret=True)


def _workload(seed=0, n_docs=5, n_queries=12, minimize=True, **opts):
    dtd = DTD.generate(n_tags=24, seed=seed)
    d = TagDictionary()
    dtd.register(d)
    profiles = gen_profiles(dtd, n=n_queries, length=3, p_desc=0.4,
                            p_wild=0.15, seed=seed)
    docs = gen_corpus(dtd, n_docs=n_docs, nodes_per_doc=60, seed=seed)
    nfa = compile_queries(profiles, d, shared=True)
    eng = engines.create("streaming", nfa, dictionary=d,
                         minimize=minimize, **{**KERNEL_OPTS, **opts})
    return eng, d, docs, dtd


def _assert_dense_parity(sp, dense):
    back = sp.densify()
    np.testing.assert_array_equal(back.matched, dense.matched)
    np.testing.assert_array_equal(back.first_event, dense.first_event)


def _pallas_eqns(jaxpr):
    """Every pallas_call equation reachable from ``jaxpr``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for v in eqn.params.values():
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                found.extend(_pallas_eqns(v.jaxpr))
            elif isinstance(v, jax.extend.core.Jaxpr):
                found.extend(_pallas_eqns(v))
    return found


# ------------------------------------------------------- route taxonomy
class TestPathTaxonomy:
    def test_kernel_fused_is_default(self):
        eng, d, docs, _ = _workload()
        batch = EventBatch.from_streams(docs, bucket=64)
        sp = eng.filter_batch_sparse(batch)
        assert sp.meta["path"] == "kernel-fused"
        assert not sp.overflowed
        _assert_dense_parity(sp, eng.filter_batch(batch))

    def test_lane_compact_when_epilogue_off_or_cap_too_big(self):
        eng, d, docs, _ = _workload(sparse_epilogue="off")
        batch = EventBatch.from_streams(docs, bucket=64)
        sp = eng.filter_batch_sparse(batch)
        assert sp.meta["path"] == "lane-compact"
        _assert_dense_parity(sp, eng.filter_batch(batch))
        # "auto" routes by the VMEM budget: a cap past it compacts lanes
        auto, _, _, _ = _workload()
        assert not auto._fused_sparse_ok(10**7)
        assert auto._fused_sparse_ok(1024)

    def test_base_fallback_for_scan_engines(self):
        eng, d, docs, _ = _workload(kernel="scan")
        batch = EventBatch.from_streams(docs, bucket=64)
        sp = eng.filter_batch_sparse(batch)
        assert sp.meta["path"] == "base-fallback"
        assert sp.meta["base_path"] == "device-compact"
        _assert_dense_parity(sp, eng.filter_batch(batch))

    def test_dense_overflow_names_attempted_path(self):
        eng, d, docs, _ = _workload()
        batch = EventBatch.from_streams(docs, bucket=64)
        sp = eng.filter_batch_sparse(batch, match_cap=1)
        assert sp.n_matches > 1, "workload must overflow cap=1"
        assert sp.overflowed
        assert sp.meta["path"] == "dense-overflow"
        assert sp.meta["attempted_path"] == "kernel-fused"
        _assert_dense_parity(sp, eng.filter_batch(batch))

    def test_sharded_mesh_runs_fused_not_base(self):
        """The pre-PR behavior — ``mesh is not None`` silently taking
        the base compaction — is gone: the mesh route is the fused
        kernel under shard_map, and says so."""
        eng, d, docs, _ = _workload()
        batch = EventBatch.from_streams(docs, bucket=64)
        sharded = eng.plan_sharded(2)
        mesh = make_filter_mesh(2)
        sp = eng.filter_batch_sharded_sparse(batch, sharded, mesh=mesh)
        assert sp.meta["path"] == "kernel-fused"
        _assert_dense_parity(sp, eng.filter_batch_sharded(batch, sharded))

    def test_bytes_path_is_one_launch(self):
        eng, d, docs, _ = _workload()
        bb = ByteBatch.from_streams(docs, bucket=256)
        batch = EventBatch.from_streams(docs, bucket=64)
        for pack in (False, True):
            sp = eng.filter_bytes_sparse(bb, pack=pack)
            assert sp.meta["path"] == "kernel-fused"
            assert sp.meta["launch"] == "bytes"
            _assert_dense_parity(sp, eng.filter_batch(batch))

    def test_stage_counts_batches_per_route(self):
        """A sparse FilterStage tallies each batch's route, so a run
        can show that every batch stayed on the fused kernel."""
        from repro.core.events import encode_bytes
        from repro.data.filter_stage import TEXT_FILL, FilterStage

        eng, d, docs, dtd = _workload(n_docs=6)
        profiles = gen_profiles(dtd, n=12, length=3, p_desc=0.4,
                                p_wild=0.15, seed=0)
        stage = FilterStage(profiles, d, engine="streaming", sparse=True,
                            batch_size=3, engine_options=KERNEL_OPTS)
        raw = [encode_bytes(x, text_fill=TEXT_FILL) for x in docs]
        list(stage.route_bytes(raw))
        assert stage.stats["verdict_paths"] == {"kernel-fused": 2}

    def test_sharded2d_sparse_fused(self):
        eng, d, docs, _ = _workload(n_docs=6)
        batch = EventBatch.from_streams(docs, bucket=64)
        sharded = eng.plan_sharded(2)
        mesh = make_filter_mesh(2, data_shards=2)
        sp = eng.filter_batch_sharded2d_sparse(batch, sharded, mesh=mesh)
        assert sp.meta["path"] == "kernel-fused"
        _assert_dense_parity(
            sp, eng.filter_batch_sharded2d(batch, sharded, mesh=mesh))


# -------------------------------------------------- overflow boundaries
class TestOverflowBoundaries:
    @pytest.mark.parametrize("route", ["plain", "sharded", "bytes",
                                       "churned"])
    def test_cap_boundary_sweep(self, route):
        eng, d, docs, _ = _workload(seed=1)
        batch = EventBatch.from_streams(docs, bucket=64)
        bb = ByteBatch.from_streams(docs, bucket=256)
        sharded = eng.plan_sharded(3)
        if route == "churned":
            sharded = sharded.remove_queries([1, 4])

        def run(cap):
            if route == "plain":
                return (eng.filter_batch_sparse(batch, match_cap=cap),
                        eng.filter_batch(batch))
            if route == "bytes":
                return (eng.filter_bytes_sparse(bb, match_cap=cap),
                        eng.filter_batch(batch))
            return (eng.filter_batch_sharded_sparse(
                        batch, sharded, match_cap=cap),
                    eng.filter_batch_sharded(batch, sharded))

        n = run(batch.batch_size * eng.n_queries)[0].meta["device_rows"]
        assert n > 2, "workload must produce a few device rows"
        for cap, over in ((n, False), (n + 1, False), (n - 1, True)):
            sp, dense = run(cap)
            assert sp.overflowed == over, (route, cap)
            assert sp.meta["path"] == ("dense-overflow" if over
                                       else "kernel-fused")
            _assert_dense_parity(sp, dense)

    def test_zero_matches(self):
        """Profiles over a disjoint tag alphabet: zero rows, no
        overflow, an empty exact densify."""
        dtd_docs = DTD.generate(n_tags=12, seed=2)
        dtd_qs = DTD.generate(n_tags=12, seed=99)
        d = TagDictionary()
        dtd_docs.register(d)
        dtd_qs.register(d)
        profiles = gen_profiles(dtd_qs, n=6, length=3, p_desc=0.4,
                                p_wild=0.0, seed=2)
        docs = gen_corpus(dtd_docs, n_docs=4, nodes_per_doc=40, seed=2)
        nfa = compile_queries(profiles, d, shared=True)
        eng = engines.create("streaming", nfa, dictionary=d,
                             minimize=True, **KERNEL_OPTS)
        batch = EventBatch.from_streams(docs, bucket=64)
        sp = eng.filter_batch_sparse(batch, match_cap=4)
        assert sp.n_matches == 0 and not sp.overflowed
        assert sp.meta["path"] == "kernel-fused"
        assert sp.meta["device_rows"] == 0
        _assert_dense_parity(sp, eng.filter_batch(batch))

    def test_all_docs_match_all_classes(self):
        """``//*`` profiles: every document hits every accept class —
        the densest possible buffer still round-trips exactly, and one
        row less than needed overflows."""
        dtd = DTD.generate(n_tags=8, seed=3)
        d = TagDictionary()
        dtd.register(d)
        profiles = [parse("//*")] * 3 + gen_profiles(dtd, n=3, length=1,
                                                     p_desc=1.0,
                                                     p_wild=1.0, seed=3)
        docs = gen_corpus(dtd, n_docs=4, nodes_per_doc=20, seed=3)
        nfa = compile_queries(profiles, d, shared=True)
        eng = engines.create("streaming", nfa, dictionary=d,
                             minimize=True, **KERNEL_OPTS)
        batch = EventBatch.from_streams(docs, bucket=64)
        dense = eng.filter_batch(batch)
        assert dense.matched.all()
        n = eng.filter_batch_sparse(batch).meta["device_rows"]
        exact = eng.filter_batch_sparse(batch, match_cap=n)
        assert not exact.overflowed and exact.meta["device_rows"] == n
        _assert_dense_parity(exact, dense)
        spill = eng.filter_batch_sparse(batch, match_cap=n - 1)
        assert spill.overflowed
        _assert_dense_parity(spill, dense)


# --------------------------------------------------- no bitmap in HBM
class TestNoBitmapInHBM:
    def test_fused_program_outputs_only_buffer_and_counter(self):
        eng, d, docs, _ = _workload()
        batch = EventBatch.from_streams(docs, bucket=64)
        kind, tag = eng._prep(batch)
        lane_cls, _, _ = eng._plain_lane_tables(eng.plan_)
        p, meta = eng.plan_, eng.plan_.meta
        cap = 64
        doc_ids = jnp.arange(batch.batch_size, dtype=jnp.int32)[:, None]

        def fused():
            return sf.stream_filter_pallas_sparse(
                sf.fuse_events(kind, tag), doc_ids,
                p["kb_tagmask"], p["kb_pw"], p["kb_pb"],
                p["kb_selfloop"], p["kb_init"],
                p["kb_acc_word"], p["kb_acc_bit"], jnp.asarray(lane_cls),
                cap=cap, max_depth=meta["max_depth"],
                chunk=meta["chunk"], interpret=True)

        calls = _pallas_eqns(jax.make_jaxpr(fused)().jaxpr)
        assert len(calls) == 1, "fusion means ONE pallas_call"
        rows = sf._buffer_rows(cap)
        shapes = sorted(tuple(v.aval.shape) for v in calls[0].outvars)
        assert shapes == sorted([(3, rows, sf.LANES), (1, 1)]), (
            "the fused program may emit ONLY the bounded match buffer "
            f"and its counter, got {shapes}")
        assert all(np.prod(s) <= 3 * rows * sf.LANES for s in shapes), \
            "no (B, G, QB) accept bitmap may reach HBM"

    def test_dense_program_does_materialize_the_bitmap(self):
        """Contrast case: the unfused kernel's outputs are the dense
        per-lane buffers — what the tentpole removed from the sparse
        hot path."""
        eng, d, docs, _ = _workload()
        batch = EventBatch.from_streams(docs, bucket=64)
        kind, tag = eng._prep(batch)
        p, meta = eng.plan_, eng.plan_.meta

        def dense():
            return sf.stream_filter_pallas(
                sf.fuse_events(kind, tag),
                p["kb_tagmask"], p["kb_pw"], p["kb_pb"],
                p["kb_selfloop"], p["kb_init"],
                p["kb_acc_word"], p["kb_acc_bit"],
                max_depth=meta["max_depth"], chunk=meta["chunk"],
                interpret=True)

        calls = _pallas_eqns(jax.make_jaxpr(dense)().jaxpr)
        bitmap = (batch.batch_size, meta["n_blocks"])
        assert any(tuple(v.aval.shape[:2]) == bitmap for c in calls
                   for v in c.outvars)


# ------------------------------------------------- kernel vs ref oracle
class TestKernelVsOracle:
    @pytest.mark.parametrize("grid_order", ["bg", "gb"])
    def test_event_kernel_matches_oracle_rows(self, grid_order):
        eng, d, docs, _ = _workload(seed=4, grid_order=grid_order)
        batch = EventBatch.from_streams(docs, bucket=64)
        kind, tag = eng._prep(batch)
        lane_cls, _, _ = eng._plain_lane_tables(eng.plan_)
        p, meta = eng.plan_, eng.plan_.meta
        ev = sf.fuse_events(kind, tag)
        args = (p["kb_tagmask"], p["kb_pw"], p["kb_pb"],
                p["kb_selfloop"], p["kb_init"],
                p["kb_acc_word"], p["kb_acc_bit"])
        mb, fb = sf.stream_filter_pallas(
            ev, *args, max_depth=meta["max_depth"], chunk=meta["chunk"],
            interpret=True, grid_order=grid_order)
        doc_ids = np.arange(batch.batch_size, dtype=np.int32)
        want_rows, want_n = ref.sparse_epilogue(
            np.asarray(mb) != 0, np.asarray(fb), lane_cls, doc_ids,
            10**6, grid_order=grid_order)
        for cap in (max(1, want_n - 1), want_n, want_n + 3):
            buf, cnt = sf.stream_filter_pallas_sparse(
                ev, jnp.asarray(doc_ids[:, None]), *args,
                jnp.asarray(lane_cls), cap=cap,
                max_depth=meta["max_depth"], chunk=meta["chunk"],
                interpret=True, grid_order=grid_order)
            assert int(np.asarray(cnt)[0, 0]) == want_n
            got = np.asarray(buf)[:min(want_n, cap)]
            exp, _ = ref.sparse_epilogue(
                np.asarray(mb) != 0, np.asarray(fb), lane_cls, doc_ids,
                cap, grid_order=grid_order)
            np.testing.assert_array_equal(got, exp)

    def test_bytes_kernel_matches_engine_oracle(self):
        """Segment-packed bytes launch (ragged docs sharing grid slots,
        pad slots dropped in-kernel) against the scan-engine truth."""
        eng, d, docs, _ = _workload(seed=5, pack=True)
        bb = ByteBatch.from_streams(docs, bucket=256)
        sp = eng.filter_bytes_sparse(bb, pack=True)
        assert sp.meta["path"] == "kernel-fused"
        scan = engines.create(
            "streaming", eng.nfa, dictionary=d, kernel="scan",
            minimize=True)
        _assert_dense_parity(sp, scan.filter_bytes(bb))
