"""Fault-tolerance tests: typed document errors, poison quarantine,
shadow-plan hot swap, and the crash-safe plan cache.

The containment contract under test (ISSUE: fault-tolerant serving):
a bad *document* — malformed bytes, over-depth nesting, a payload that
makes the device call raise — fails only the requests that carried it,
with a typed :class:`~repro.core.events.DocumentError`, while every
co-batched healthy request gets the bit-identical verdict a fault-free
run computes.  Subscription changes build on a shadow thread and commit
atomically at a batch boundary (or roll back, leaving the serving plan
untouched), and compiled plans persist in a content-addressed cache
whose entries survive torn writes.
"""
import os
import threading

import numpy as np
import pytest

from _hypothesis_shim import given, settings, st

from repro.checkpoint.store import (CheckpointStore, PlanCache,
                                    _valid_entry, _write_entry,
                                    _write_pointer)
from repro.core import engines
from repro.core.dictionary import TagDictionary
from repro.core.events import (DEFAULT_MAX_DEPTH, DepthOverflow,
                               DocumentError, KernelFault,
                               MalformedDocument, encode_bytes,
                               validate_payload)
from repro.core.nfa import compile_queries
from repro.data.filter_stage import (TEXT_FILL, FilterStage, PlanEpoch,
                                     StalePlanError)
from repro.data.generator import DTD, gen_corpus, gen_profiles
from repro.serve.faults import (DEFAULT_PLAN, FaultInjector, FaultPlan,
                                run_chaos_trace)
from repro.serve.loop import ServeLoop

ENGINE = "streaming"
N_QUERIES = 16
BATCH = 4


def _workload(n_docs=16, seed=0):
    dtd = DTD.generate(n_tags=24, seed=seed)
    d = TagDictionary()
    dtd.register(d)
    profiles = gen_profiles(dtd, n=N_QUERIES, length=3, seed=seed)
    docs = gen_corpus(dtd, n_docs=n_docs, nodes_per_doc=40, seed=1)
    raw = [encode_bytes(x, text_fill=TEXT_FILL) for x in docs]
    return profiles, d, dtd, raw


def _stage(profiles, d, **kw):
    kw.setdefault("engine", ENGINE)
    kw.setdefault("keep_unmatched", True)
    kw.setdefault("batch_size", BATCH)
    return FilterStage(profiles, d, n_shards=2, **kw)


def _nested(d, depth):
    return (b"".join(d.open_bytes(0) for _ in range(depth))
            + b"".join(d.close_bytes(0) for _ in range(depth)))


def _routes(tickets):
    return {(rd.doc_index, rd.shard): tuple(rd.matched_profiles)
            for t in tickets if not t.shed and not t.failed
            for rd in t.routed}


# ------------------------------------------------------- error taxonomy
class TestValidatePayload:
    def test_well_formed_corpus_validates(self):
        _, d, _, raw = _workload()
        for buf in raw:
            validate_payload(buf)  # must not raise

    def test_empty_payload_is_valid(self):
        validate_payload(b"")

    def test_unclosed_element_is_malformed(self):
        _, d, _, _ = _workload()
        with pytest.raises(MalformedDocument, match="unclosed"):
            validate_payload(d.open_bytes(0))

    def test_close_without_open_is_malformed(self):
        _, d, _, _ = _workload()
        with pytest.raises(MalformedDocument, match="without matching"):
            validate_payload(d.close_bytes(0))

    def test_undecodable_marker_is_malformed(self):
        with pytest.raises(MalformedDocument, match="undecodable"):
            validate_payload(b"<\xff\xff")

    def test_overdepth_is_depth_overflow(self):
        _, d, _, _ = _workload()
        with pytest.raises(DepthOverflow, match="max_depth"):
            validate_payload(_nested(d, DEFAULT_MAX_DEPTH + 1))

    def test_taxonomy_is_value_error(self):
        """Typed errors keep every pre-existing ``except ValueError``
        contract intact, and carry per-document attribution."""
        assert issubclass(MalformedDocument, DocumentError)
        assert issubclass(DepthOverflow, DocumentError)
        assert issubclass(KernelFault, DocumentError)
        assert issubclass(DocumentError, ValueError)
        e = DepthOverflow("deep", (3, 5))
        assert e.doc_indices == (3, 5)

    def test_agrees_with_the_device_predecoder(self):
        """The check looks at the ``<`` bytes alone; on random payloads
        it reaches the verdict that the device pre-decoder's per-byte
        classification (:func:`repro.kernels.ref.predecode`) implies:
        the first ``<`` it drops, then the depth walk of its events."""
        from repro.kernels import ref

        rng = np.random.default_rng(5)
        alphabet = np.frombuffer(b"<<<<//ab_.Z9x >\x00", np.uint8)
        bufs = [rng.choice(alphabet, size=int(n)).tobytes()
                for n in rng.integers(0, 24, size=400)]
        bufs.append(b"<ab>" * 3 + b"</ab>" * 3)
        data = np.zeros((len(bufs), 32), np.uint8)
        for i, b in enumerate(bufs):
            data[i, :len(b)] = np.frombuffer(b, np.uint8)
        kinds = np.asarray(ref.predecode(data)[0])
        seen = set()
        for buf, row, kind in zip(bufs, data, kinds):
            dropped = np.flatnonzero((row == ord("<")) & (kind == ref.PAD))
            depth = np.cumsum(np.where(kind == ref.OPEN, 1,
                                       np.where(kind == ref.CLOSE, -1, 0)))
            if dropped.size:
                want = f"undecodable tag marker at byte {dropped[0]}"
            elif depth.min() < 0:
                want = "close tag without matching open"
            elif depth[-1] != 0:
                want = "unclosed elements"
            elif depth.max() > 2:
                want = "exceeds max_depth"
            else:
                want = None
                validate_payload(buf, max_depth=2)
            seen.add(want and want.split()[0])
            if want:
                with pytest.raises(DocumentError, match=want):
                    validate_payload(buf, max_depth=2)
        assert seen == {None, "undecodable", "close", "unclosed", "exceeds"}

    @given(depth=st.integers(min_value=1, max_value=2 * DEFAULT_MAX_DEPTH))
    @settings(max_examples=20, deadline=None)
    def test_depth_boundary_property(self, depth):
        """Nesting validates iff it fits the parser's bounded stack —
        the host check mirrors kernel semantics exactly."""
        d = TagDictionary()
        d.add("a")
        buf = _nested(d, depth)
        if depth <= DEFAULT_MAX_DEPTH:
            validate_payload(buf)
        else:
            with pytest.raises(DepthOverflow):
                validate_payload(buf)


class TestTypedErrorsOnRoutes:
    def test_route_bytes_overdepth_raises_typed(self):
        """The parse-path device route raises a typed ``DepthOverflow``
        (a ``ValueError``) naming the offending batch rows.  The
        streaming engine's fused byte path clips depth in-kernel
        instead of raising — which is exactly why the serve loop
        validates pre-admission (see the loop tests below)."""
        profiles, d, _, raw = _workload(n_docs=BATCH)
        stage = _stage(profiles, d, engine="levelwise")
        bad = raw[:2] + [_nested(d, DEFAULT_MAX_DEPTH + 16)] + raw[3:4]
        with pytest.raises(DepthOverflow) as ei:
            list(stage.route_bytes(bad))
        assert isinstance(ei.value, ValueError)
        assert 2 in ei.value.doc_indices

    @pytest.mark.parametrize("kw", [
        {}, {"sparse": True}, {"query_shards": 2},
        {"query_shards": 2, "data_shards": 2},
    ], ids=["dense", "sparse", "sharded", "mesh2d"])
    def test_loop_rejects_poison_on_every_route(self, kw):
        """Whatever route config serves the loop, malformed and
        over-depth payloads are rejected pre-admission with typed
        errors and the healthy co-submitted documents still get the
        fault-free verdicts."""
        profiles, d, _, raw = _workload(n_docs=6)
        want = _routes_ref(profiles, d, raw)
        loop = ServeLoop(_stage(profiles, d, **kw), max_batch=BATCH,
                         deadline_ms=60_000, queue_cap=64)
        with loop:
            bad_m = loop.submit(d.open_bytes(0))
            bad_d = loop.submit(_nested(d, DEFAULT_MAX_DEPTH + 1))
            tickets = [loop.submit(p) for p in raw]
        assert isinstance(bad_m.error, MalformedDocument)
        assert isinstance(bad_d.error, DepthOverflow)
        assert bad_m.seq == -1 and bad_d.seq == -1  # never admitted
        assert _routes(tickets) == want
        s = loop.slo_summary()
        assert s["rejected"] == 2 and s["quarantined"] == 2
        assert s["completed"] == len(raw)
        assert len(loop.dead_letter) == 2


def _routes_ref(profiles, d, raw):
    return {(r.doc_index, r.shard): tuple(r.matched_profiles)
            for b in _stage(profiles, d).route_bytes(raw) for r in b}


# -------------------------------------------------- quarantine/bisection
class _Poisoner:
    """Make the stage's batch call raise an *untyped* error whenever a
    marked payload is present — the loop must bisect to find it."""

    def __init__(self, stage, poison: set):
        self.poison = poison
        self.stage = stage
        self.calls = 0
        self._orig = stage._filter_bytebatch
        stage._filter_bytebatch = self._filter

    def _filter(self, bufs, record=True, epoch=None):
        self.calls += 1
        if any(b in self.poison for b in bufs):
            raise RuntimeError("poisoned batch")
        return self._orig(bufs, record=record, epoch=epoch)


class TestQuarantine:
    def _run(self, poison_at, n_docs=8):
        profiles, d, _, raw = _workload(n_docs=n_docs)
        healthy = [i for i in range(n_docs) if i not in poison_at]
        want = _routes_ref(profiles, d, [raw[i] for i in healthy])
        # poison payloads stay *valid* bytes (pass pre-admission);
        # uniqueness markers make them detectable by the poisoner
        marked = dict(enumerate(raw))
        for i in poison_at:
            marked[i] = raw[i] + d.open_bytes(1) + d.close_bytes(1)
        stage = _stage(profiles, d)
        _Poisoner(stage, {marked[i] for i in poison_at})
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        with loop:
            tickets = [loop.submit(marked[i]) for i in range(n_docs)]
        return loop, tickets, healthy, want

    def test_single_poison_quarantined_as_kernel_fault(self):
        loop, tickets, healthy, _ = self._run({2})
        t = tickets[2]
        assert t.failed and isinstance(t.error, KernelFault)
        assert t.error.doc_indices == (t.seq,)
        assert t.error.__cause__ is not None  # original fault chained
        s = loop.slo_summary()
        assert s["quarantined"] == 1 and s["failed"] == 0
        assert s["retries"] >= 1  # whole-batch retry ran before bisection
        recs = list(loop.dead_letter)
        assert len(recs) == 1 and recs[0]["error"] == "KernelFault"

    def test_healthy_verdicts_survive_quarantine(self):
        """Co-batched healthy documents get bit-identical verdicts —
        quarantine isolates, it never corrupts."""
        loop, tickets, healthy, want = self._run({2})
        got = {(rd.doc_index, rd.shard): tuple(rd.matched_profiles)
               for i in healthy for rd in tickets[i].routed}
        # doc_index is the per-delivery-batch row; compare the verdict
        # *sets* per shard instead (row numbering shifts when a poisoned
        # row is cut out)
        assert _verdict_sets(got) == _verdict_sets(want)

    @given(pos=st.sets(st.integers(min_value=0, max_value=7),
                       min_size=1, max_size=3))
    @settings(max_examples=5, deadline=None)
    def test_any_poison_subset_is_contained(self, pos):
        """Property: wherever the poison lands in the batch stream, the
        loop quarantines exactly those requests and completes the rest
        with fault-free verdicts."""
        loop, tickets, healthy, want = self._run(pos)
        for i in pos:
            assert tickets[i].failed
            assert isinstance(tickets[i].error, KernelFault)
        for i in healthy:
            assert not tickets[i].failed and tickets[i].routed is not None
        got = {(rd.doc_index, rd.shard): tuple(rd.matched_profiles)
               for i in healthy for rd in tickets[i].routed}
        assert _verdict_sets(got) == _verdict_sets(want)
        s = loop.slo_summary()
        assert s["quarantined"] == len(pos)
        assert s["arrived"] == (s["completed"] + s["shed"] + s["failed"]
                                + s["quarantined"])


class TestSystemicFault:
    """An error that every subset reproduces is the device path's (a
    lowering, compile or runtime fault), not a document's: it fails the
    loop instead of becoming a dead letter per request."""

    def test_every_subset_failing_fails_the_loop(self):
        profiles, d, _, raw = _workload(n_docs=8)
        stage = _stage(profiles, d)
        _Poisoner(stage, set(raw))     # every payload fails: a broken path
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        tickets = [loop.submit(p) for p in raw]
        with pytest.raises(RuntimeError, match="poisoned batch"):
            loop.close()
        for t in tickets:
            assert t.failed and not isinstance(t.error, KernelFault)
        s = loop.slo_summary()
        assert s["quarantined"] == 0 and len(loop.dead_letter) == 0
        assert s["failed"] == len(raw)
        assert s["arrived"] == (s["completed"] + s["shed"] + s["failed"]
                                + s["quarantined"])

    def test_lone_poison_after_a_served_batch_is_quarantined(self):
        """A poison document alone in its batch: a payload served
        earlier still filters, so the fault is the document's."""
        profiles, d, _, raw = _workload(n_docs=BATCH)
        bad = raw[0] + d.open_bytes(1) + d.close_bytes(1)
        stage = _stage(profiles, d)
        _Poisoner(stage, {bad})
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        with loop:
            healthy = [loop.submit(p) for p in raw]
            for t in healthy:
                assert t.done.wait(timeout=120)
            lone = loop.submit(bad)        # flushed alone at close()
        assert all(not t.failed for t in healthy)
        assert lone.failed and isinstance(lone.error, KernelFault)
        s = loop.slo_summary()
        assert s["quarantined"] == 1 and s["failed"] == 0
        assert [r["payload"] for r in loop.dead_letter] == [bad]

    def test_serve_cli_exits_nonzero(self, monkeypatch):
        """``python -m repro.launch.serve --arrival …`` raises out of
        ``main`` (a non-zero exit) when the device path is broken."""
        import sys

        import repro.launch.serve as serve

        def broken(self, bufs, record=True, epoch=None):
            raise RuntimeError("lowering failed")

        monkeypatch.setattr(FilterStage, "_filter_bytebatch", broken)
        monkeypatch.setattr(sys, "argv", [
            "serve", "--requests", "4", "--replicas", "1", "--batch", "2",
            "--prompt-len", "4", "--gen-len", "2", "--arrival", "replay",
            "--rate", "2000", "--filter-engine", ENGINE])
        with pytest.raises(RuntimeError, match="lowering failed"):
            serve.main()


def _verdict_sets(routes: dict) -> dict:
    out: dict[int, list] = {}
    for (_, shard), matched in sorted(routes.items()):
        out.setdefault(shard, []).append(tuple(sorted(matched)))
    return {k: sorted(v) for k, v in out.items()}


# ------------------------------------------------------------ accounting
class TestAccountingAndClose:
    def test_accounting_closes_with_mixed_outcomes(self):
        profiles, d, _, raw = _workload(n_docs=8)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=60_000, queue_cap=64)
        with loop:
            loop.submit(d.open_bytes(0))          # rejected
            for p in raw:
                loop.submit(p)                    # completed
        s = loop.slo_summary()
        assert s["arrived"] == s["admitted"] + s["shed"] + s["rejected"]
        assert s["arrived"] == (s["completed"] + s["shed"] + s["failed"]
                                + s["quarantined"])
        assert s["dead_letter_depth"] == 1

    def test_close_is_idempotent_and_reentrant(self):
        profiles, d, _, raw = _workload(n_docs=2)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=5, queue_cap=8)
        with loop:
            ts = [loop.submit(p) for p in raw]
        loop.close()   # second close after __exit__: no-op
        loop.close()   # third: still a no-op
        assert all(t.done.is_set() for t in ts)

    def test_concurrent_close_from_two_threads(self):
        profiles, d, _, raw = _workload(n_docs=2)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=5, queue_cap=8)
        for p in raw:
            loop.submit(p)
        t = threading.Thread(target=loop.close)
        t.start()
        loop.close()
        t.join(timeout=120)
        assert not t.is_alive()

    def test_submit_after_close_sheds(self):
        profiles, d, _, raw = _workload(n_docs=1)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=5, queue_cap=8)
        loop.close()
        t = loop.submit(raw[0])
        assert t.shed and t.done.is_set()

    def test_dead_letter_buffer_is_bounded(self):
        profiles, d, _, _ = _workload(n_docs=1)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=5, queue_cap=8, dead_letter_cap=3)
        with loop:
            for _ in range(10):
                loop.submit(d.open_bytes(0))
        assert len(loop.dead_letter) == 3
        assert loop.slo_summary()["rejected"] == 10


# ---------------------------------------------------- shadow-plan hot swap
class TestShadowSwap:
    def test_prepare_commit_subscribe(self):
        profiles, d, dtd, raw = _workload()
        stage = _stage(profiles, d, query_shards=2)
        q = gen_profiles(dtd, n=1, length=3, seed=50)[0]
        ep0 = stage.plan_epoch()
        pending = stage.prepare_subscribe(q)
        gid = stage.commit(pending)
        assert gid == N_QUERIES
        assert stage.plan_epoch().epoch == ep0.epoch + 1

    def test_stale_prepare_raises_and_retry_succeeds(self):
        """A prepare built against a superseded epoch must NOT commit
        (it would silently drop the interleaved change)."""
        profiles, d, dtd, raw = _workload()
        stage = _stage(profiles, d, query_shards=2)
        qa, qb = gen_profiles(dtd, n=2, length=3, seed=51)
        pending = stage.prepare_subscribe(qa)
        stage.subscribe(qb)                      # interleaved: epoch bump
        with pytest.raises(StalePlanError):
            stage.commit(pending)
        gid = stage.commit(stage.prepare_subscribe(qa))  # rebuilt: fine
        assert gid in stage.sharded_.live_ids()

    def test_epoch_pins_inflight_batch_plan(self):
        """A batch filtered against an epoch-N snapshot fans out with
        epoch N's plan and gid table even after a swap commits — the
        in-flight-batch consistency the loop's workers rely on."""
        profiles, d, dtd, raw = _workload(n_docs=BATCH)
        stage = _stage(profiles, d, query_shards=2)
        want = {(r.doc_index, r.shard): tuple(r.matched_profiles)
                for b in stage.route_bytes(raw) for r in b}
        ep = stage.plan_epoch()
        assert isinstance(ep, PlanEpoch)
        stage.subscribe(gen_profiles(dtd, n=1, length=3, seed=52)[0])
        assert stage.plan_epoch().epoch == ep.epoch + 1
        res = stage._filter_bytebatch(raw, record=False, epoch=ep)
        routed = stage._fan_out(res, [len(p) for p in raw], gids=ep.gids)
        got = {(r.doc_index, r.shard): tuple(r.matched_profiles)
               for r in routed}
        assert got == want
        assert np.array_equal(np.sort(np.asarray(ep.gids)),
                              np.arange(N_QUERIES))

    def test_loop_subscribe_swaps_without_drain(self):
        """A live subscribe through the loop: the ticket commits, and
        later documents match the new profile — all while the loop
        keeps serving (no queue drain, no restart)."""
        profiles, d, dtd, raw = _workload(n_docs=12)
        stage = _stage(profiles, d, query_shards=2)
        # warm post-swap shapes so the swap is a table swap, not a
        # recompile (pads never shrink on unsubscribe)
        q = gen_profiles(dtd, n=1, length=3, seed=53)[0]
        g = stage.subscribe(q)
        list(stage.route_bytes(raw))
        stage.unsubscribe(g)
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        with loop:
            pre = [loop.submit(p) for p in raw[:BATCH]]
            tk = loop.subscribe(q)
            assert tk.done.wait(timeout=120)
            post = [loop.submit(p) for p in raw[BATCH:]]
        assert tk.error is None and tk.gid is not None
        assert loop.slo_summary()["swaps"] == 1
        sw = loop.swap_summary()
        assert sw["swaps"] == 1 and sw["swap_rollbacks"] == 0
        assert np.isfinite(sw["commit_p50_ms"])
        # every pre-swap verdict is for the old gid set only
        for t in pre:
            for rd in t.routed:
                assert all(int(x) < N_QUERIES
                           for x in np.asarray(rd.matched_profiles))
        assert all(not t.failed for t in pre + post)

    def test_failed_shadow_build_rolls_back(self):
        """A prepare that raises must leave the serving plan untouched
        and surface the error on the ticket — never kill the loop."""
        profiles, d, dtd, raw = _workload(n_docs=8)
        stage = _stage(profiles, d, query_shards=2)
        orig = stage.prepare_subscribe
        stage.prepare_subscribe = lambda q: (_ for _ in ()).throw(
            RuntimeError("shadow build exploded"))
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        with loop:
            tk = loop.subscribe(gen_profiles(dtd, n=1, length=3,
                                             seed=54)[0])
            assert tk.done.wait(timeout=120)
            assert tk.error is not None
            assert "shadow build exploded" in str(tk.error)
            stage.prepare_subscribe = orig
            tickets = [loop.submit(p) for p in raw]   # loop still serves
        assert all(not t.failed for t in tickets)
        s = loop.slo_summary()
        assert s["swap_rollbacks"] == 1 and s["swaps"] == 0
        assert s["completed"] == len(raw)


# ------------------------------------------------------------- plan cache
class TestPlanCache:
    def test_put_get_roundtrip(self, tmp_path):
        cache = PlanCache(str(tmp_path))
        tables = {"a": np.arange(6).reshape(2, 3),
                  "b": np.ones(4, np.float32)}
        cache.put("k1", tables, {"meta": 1})
        hit = cache.get("k1")
        assert hit is not None
        got, manifest = hit
        assert np.array_equal(got["a"], tables["a"])
        assert manifest["meta"] == 1
        assert cache.hits == 1 and cache.misses == 0
        assert cache.keys() == ["k1"]

    def test_miss_and_corrupt_entry(self, tmp_path):
        cache = PlanCache(str(tmp_path))
        assert cache.get("nope") is None and cache.misses == 1
        cache.put("k", {"a": np.zeros(2)})
        os.remove(os.path.join(cache._path("k"), "manifest.json"))
        assert cache.get("k") is None       # torn entry reads as a miss
        assert "k" not in cache
        cache.put("k", {"a": np.ones(2)})   # and is overwritten cleanly
        assert np.array_equal(cache.get("k")[0]["a"], np.ones(2))

    def test_warm_cache_skips_recompilation(self, tmp_path):
        """The crash-recovery contract: a rebuilt engine against a warm
        cache is all hits, no misses — and plans identically."""
        profiles, d, dtd, raw = _workload()
        nfa = compile_queries(d.rewrite_profile_tags(profiles), d,
                              shared=True)
        cold = PlanCache(str(tmp_path))
        eng = engines.create(ENGINE, nfa, dictionary=d, plan_cache=cold)
        sp = eng.plan_sharded(2)
        assert cold.misses > 0
        warm = PlanCache(str(tmp_path))
        eng2 = engines.create(ENGINE, nfa, dictionary=d, plan_cache=warm)
        sp2 = eng2.plan_sharded(2)
        assert warm.misses == 0 and warm.hits == cold.misses
        assert dict(sp.pads) == dict(sp2.pads)

    def test_cached_stage_verdict_parity(self, tmp_path):
        """Cached-plan serving is bit-identical to compiled-from-scratch
        serving, end to end through the stage."""
        profiles, d, dtd, raw = _workload(n_docs=8)
        opts = {"plan_cache": str(tmp_path)}
        list(_stage(profiles, d, query_shards=2,
                    engine_options=opts).route_bytes(raw))  # populate
        want = {(r.doc_index, r.shard): tuple(r.matched_profiles)
                for b in _stage(profiles, d,
                                query_shards=2).route_bytes(raw)
                for r in b}
        got = {(r.doc_index, r.shard): tuple(r.matched_profiles)
               for b in _stage(profiles, d, query_shards=2,
                               engine_options=opts).route_bytes(raw)
               for r in b}
        assert got == want

    def test_key_covers_nfa_and_pads(self):
        profiles, d, dtd, raw = _workload()
        nfa = compile_queries(d.rewrite_profile_tags(profiles), d,
                              shared=True)
        eng = engines.create(ENGINE, nfa, dictionary=d)
        k1 = eng.plan_cache_key(nfa)
        k2 = eng.plan_cache_key(nfa, {"n_queries": 32, "n_states": 64})
        assert k1 != k2
        assert eng.plan_cache_key(nfa) == k1    # deterministic


# -------------------------------------------------- store crash safety
class TestStoreCrashSafety:
    def test_write_entry_is_atomic(self, tmp_path):
        d = str(tmp_path)
        final = _write_entry(d, "e1", {"x": np.arange(3)}, {"keys": ["x"]})
        assert _valid_entry(final)
        assert not os.path.exists(os.path.join(d, "e1.tmp"))

    def test_stale_tmp_dir_is_replaced(self, tmp_path):
        """A crash mid-write leaves ``<name>.tmp`` — the next write must
        clear it, and the torn dir must never read as an entry."""
        d = str(tmp_path)
        os.makedirs(os.path.join(d, "e1.tmp"))
        with open(os.path.join(d, "e1.tmp", "garbage"), "w") as f:
            f.write("torn")
        assert not _valid_entry(os.path.join(d, "e1.tmp"))
        final = _write_entry(d, "e1", {"x": np.zeros(2)}, {"keys": ["x"]})
        assert _valid_entry(final)
        assert not os.path.exists(os.path.join(d, "e1.tmp"))

    def test_pointer_update_is_atomic(self, tmp_path):
        d = str(tmp_path)
        _write_pointer(d, "LATEST", "step_00000001")
        _write_pointer(d, "LATEST", "step_00000002")
        with open(os.path.join(d, "LATEST")) as f:
            assert f.read() == "step_00000002"
        assert not os.path.exists(os.path.join(d, "LATEST.tmp"))

    def test_store_save_restores_after_torn_last_step(self, tmp_path):
        """restore_latest walks back past an invalid (torn) newest step
        — the crash-recovery path the serve loop's plan cache shares."""
        store = CheckpointStore(str(tmp_path), keep=4)
        tree = {"w": np.arange(4, dtype=np.float32)}
        store.save(1, tree, {"note": "good"})
        store.save(2, {"w": np.arange(4, dtype=np.float32) * 2})
        # tear step 2: manifest gone → invalid → walk back to step 1
        os.remove(os.path.join(str(tmp_path), "step_00000002",
                               "manifest.json"))
        step, got, manifest = store.restore_latest(tree)
        assert step == 1
        assert np.array_equal(got["w"], tree["w"])


# ------------------------------------------------------------ chaos trace
class TestChaosTrace:
    def test_default_drill_passes_every_check(self):
        """The CI chaos drill, in-suite: every containment invariant
        holds on the default fault plan."""
        report = run_chaos_trace(24, plan=FaultPlan(
            malformed=(3,), overdepth=(7,), kernel=(10,),
            worker_fault_batches=(2,), pad_overflow_adds=(2,)))
        assert report["ok"], report["checks"]
        assert report["slo"]["failed"] == 0
        errs = sorted(r["error"] for r in report["dead_letter"])
        assert errs == ["DepthOverflow", "KernelFault",
                        "MalformedDocument"]

    def test_injector_restores_stage(self):
        profiles, d, dtd, raw = _workload()
        stage = _stage(profiles, d, query_shards=2)
        orig_filter = stage._filter_bytebatch
        orig_plan = stage._eng.plan_part
        inj = FaultInjector(stage, DEFAULT_PLAN, set())
        assert stage._filter_bytebatch != orig_filter
        inj.remove()
        assert stage._filter_bytebatch == orig_filter
        assert stage._eng.plan_part == orig_plan
