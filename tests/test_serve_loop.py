"""Continuous serve-loop tests (:mod:`repro.serve.loop`).

The contract under test: the loop is *schedule*, not *semantics* —
whatever the arrival pattern, batch-close reason, pipeline depth or
overload policy, every admitted request gets the bit-identical verdict
the synchronous ``route_bytes`` path computes, delivered in admission
order per subscriber; and every bound (queue cap, K in-flight slots)
actually binds, with the corresponding counter observable.

These tests run threaded code with real deadlines — they are written so
that a *wedged* loop fails by pytest-timeout (the CI serve job runs
them under a suite-wide ``--timeout``), never by flaky sleeps: waits
are generous upper bounds, assertions never depend on tight timing.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.dictionary import TagDictionary
from repro.core.events import KernelFault, encode_bytes
from repro.data.filter_stage import TEXT_FILL, FilterStage
from repro.data.generator import DTD, gen_corpus, gen_profiles
from repro.serve.loop import (ServeLoop, burst_arrivals, make_arrivals,
                              poisson_arrivals, replay_arrivals, run_trace)

ENGINE = "streaming"   # fixed device shapes: no content-dependent compiles
N_QUERIES = 16
BATCH = 4


def _workload(n_docs=16, seed=0):
    dtd = DTD.generate(n_tags=24, seed=seed)
    d = TagDictionary()
    dtd.register(d)
    profiles = gen_profiles(dtd, n=N_QUERIES, length=3, seed=seed)
    docs = gen_corpus(dtd, n_docs=n_docs, nodes_per_doc=40, seed=1)
    raw = [encode_bytes(x, text_fill=TEXT_FILL) for x in docs]
    return profiles, d, raw


def _stage(profiles, d, **kw):
    kw.setdefault("engine", ENGINE)
    kw.setdefault("keep_unmatched", True)
    kw.setdefault("batch_size", BATCH)
    return FilterStage(profiles, d, n_shards=2, **kw)


def _routes(batches):
    return {(r.doc_index, r.shard): tuple(r.matched_profiles)
            for b in batches for r in b}


def _ticket_routes(tickets):
    return {(rd.doc_index, rd.shard): tuple(rd.matched_profiles)
            for t in tickets if not t.shed for rd in t.routed}


# ------------------------------------------------------------ batch closing
class TestAdaptiveBatching:
    def test_size_close_fires_before_deadline(self):
        profiles, d, raw = _workload(n_docs=2 * BATCH)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=60_000, queue_cap=64)
        with loop:
            tickets = [loop.submit(p) for p in raw]
            for t in tickets:
                assert t.done.wait(timeout=60), "verdict never arrived"
        s = loop.slo_summary()
        # an exact multiple of max_batch under an effectively infinite
        # deadline: every close is a size close
        assert s["size_closes"] == 2
        assert s["deadline_closes"] == 0 and s["flush_closes"] == 0
        assert s["batch_fill"] == 1.0
        assert s["completed"] == len(raw) and s["shed"] == 0

    def test_deadline_close_fires_under_size(self):
        profiles, d, raw = _workload(n_docs=BATCH - 1)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=50, queue_cap=64)
        with loop:
            tickets = [loop.submit(p) for p in raw]
            # fewer than max_batch queued and nothing else arriving: only
            # the deadline can close this batch
            for t in tickets:
                assert t.done.wait(timeout=60), "deadline close never fired"
            assert loop.slo_summary()["deadline_closes"] >= 1
        s = loop.slo_summary()
        assert s["completed"] == BATCH - 1
        assert s["size_closes"] == 0

    def test_flush_close_on_exit(self):
        profiles, d, raw = _workload(n_docs=2)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=60_000, queue_cap=64)
        with loop:
            tickets = [loop.submit(p) for p in raw]
            # no wait: close() must flush the sub-deadline remainder
        assert all(t.t_verdict is not None for t in tickets)
        assert loop.slo_summary()["flush_closes"] >= 1


# --------------------------------------------------------- admission control
class TestAdmissionControl:
    def _stalled_loop(self, profiles, d, overload, queue_cap):
        """A loop whose consumer is stalled: the completer blocks in
        deliver() holding the single in-flight slot, so the queue can
        only fill — admission at the cap is what's under test."""
        release = threading.Event()
        delivered = []

        def deliver(routed):
            delivered.append(routed)
            release.wait(timeout=120)

        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=5, queue_cap=queue_cap,
                         max_inflight=1, overload=overload,
                         deliver=deliver)
        return loop, release, delivered

    def test_shed_beyond_queue_cap(self):
        profiles, d, raw = _workload(n_docs=32)
        cap = 4
        loop, release, delivered = self._stalled_loop(profiles, d,
                                                      "shed", cap)
        try:
            tickets = [loop.submit(p) for p in raw]
            shed = [t for t in tickets if t.shed]
            # the queue is bounded: with the pipeline wedged, at most
            # cap + (in flight through the batcher) requests can be
            # admitted; the rest MUST shed, immediately (no blocking)
            assert len(shed) > 0
            s = loop.slo_summary()
            assert s["shed"] == len(shed)
            assert s["max_queue_depth"] <= cap
            assert s["admitted"] + s["shed"] == len(raw)
            # shed tickets resolve instantly, with no verdict
            for t in shed:
                assert t.done.is_set() and t.t_verdict is None
                assert t.seq == -1
        finally:
            release.set()
            loop.close()
        # everything admitted (not shed) still got its verdict
        assert loop.slo_summary()["completed"] == \
            loop.slo_summary()["admitted"]

    def test_block_at_queue_cap_stalls_producer(self):
        profiles, d, raw = _workload(n_docs=12)
        loop, release, delivered = self._stalled_loop(profiles, d,
                                                      "block", 2)
        produced = threading.Event()
        tickets = []

        def producer():
            for p in raw:
                tickets.append(loop.submit(p))
            produced.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            # the producer must wedge against the bounded queue while
            # the consumer is stalled...
            assert not produced.wait(timeout=1.0), \
                "submit() never blocked at queue_cap under block policy"
        finally:
            release.set()
            # ...and drain completely once the consumer resumes
            assert produced.wait(timeout=120), "producer stayed blocked"
            t.join(timeout=120)
            loop.close()
        s = loop.slo_summary()
        assert s["shed"] == 0
        assert s["completed"] == len(raw)
        assert all(not t_.shed for t_ in tickets)

    def test_backpressure_counter_under_stalled_consumer(self):
        profiles, d, raw = _workload(n_docs=16)
        loop, release, delivered = self._stalled_loop(profiles, d,
                                                      "shed", 16)
        try:
            for p in raw:
                loop.submit(p)
            # K=1 and a stalled consumer: the batcher must report
            # waiting on an in-flight slot
            deadline = time.monotonic() + 60
            while (loop.slo_summary()["backpressure_waits"] == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert loop.slo_summary()["backpressure_waits"] >= 1
        finally:
            release.set()
            loop.close()


# ------------------------------------------------------ parity & ordering
class TestParity:
    @pytest.mark.parametrize("max_inflight", [1, 2, 4])
    def test_verdicts_bit_identical_to_route_bytes(self, max_inflight):
        """K-deep pipelining parity: whatever K, verdicts equal the
        synchronous path bit for bit and arrive in order."""
        profiles, d, raw = _workload(n_docs=17)  # ragged tail on purpose
        deliveries = []
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=60_000, queue_cap=64,
                         max_inflight=max_inflight,
                         deliver=deliveries.append)
        with loop:
            tickets = [loop.submit(p) for p in raw]
        want = _routes(_stage(profiles, d).route_bytes(raw))
        assert _ticket_routes(tickets) == want
        assert _routes(deliveries) == want
        # ordered delivery per subscriber: each shard sees its documents
        # in admission order
        per_shard: dict[int, list[int]] = {}
        for batch in deliveries:
            for rd in batch:
                per_shard.setdefault(rd.shard, []).append(rd.doc_index)
        for shard, seq in per_shard.items():
            assert seq == sorted(seq), f"shard {shard} out of order: {seq}"

    def test_parity_with_deadline_closed_padded_batches(self):
        """Undersized deadline-closed batches are padded back to
        max_batch (one compiled shape) — the pad rows must never leak
        into verdicts."""
        profiles, d, raw = _workload(n_docs=10)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=1, queue_cap=64)
        assert loop.pad_batches
        with loop:
            tickets = []
            for p in raw:
                tickets.append(loop.submit(p))
                time.sleep(0.01)  # let deadlines fire mid-stream
        assert loop.slo_summary()["completed"] == len(raw)
        want = _routes(_stage(profiles, d).route_bytes(raw))
        assert _ticket_routes(tickets) == want

    def test_parity_sparse_stage(self):
        """Sparse verdict delivery through the loop (pad_batches is
        auto-disabled: match lists carry real doc ids)."""
        profiles, d, raw = _workload(n_docs=9)
        loop = ServeLoop(_stage(profiles, d, sparse=True),
                         max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        assert not loop.pad_batches
        with loop:
            tickets = [loop.submit(p) for p in raw]
        want = _routes(_stage(profiles, d).route_bytes(raw))
        assert _ticket_routes(tickets) == want

    def test_parity_2d_mesh_stage(self):
        """The loop over a 2-D (data × model) stage: the worker rides
        the sharded bytes→verdict program, parity must hold."""
        profiles, d, raw = _workload(n_docs=8)
        loop = ServeLoop(_stage(profiles, d, query_shards=2,
                                data_shards=2),
                         max_batch=BATCH, deadline_ms=60_000,
                         queue_cap=64)
        with loop:
            tickets = [loop.submit(p) for p in raw]
        want = _routes(_stage(profiles, d).route_bytes(raw))
        assert _ticket_routes(tickets) == want

    def test_latencies_and_slo_summary(self):
        profiles, d, raw = _workload(n_docs=BATCH * 2)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=60_000, queue_cap=64)
        with loop:
            tickets = [loop.submit(p) for p in raw]
        lat = loop.latencies_ms()
        assert lat.shape == (len(raw),) and (lat > 0).all()
        s = loop.slo_summary()
        assert np.isfinite([s["p50_ms"], s["p99_ms"], s["p999_ms"]]).all()
        assert s["p50_ms"] <= s["p99_ms"] <= s["p999_ms"]
        assert s["served_per_s"] > 0
        for t in tickets:
            assert t.latency_s is not None and t.latency_s > 0
        hist = loop.latency_histogram(n_bins=8)
        assert sum(hist["counts"]) == len(raw)
        assert len(hist["edges_ms"]) == len(hist["counts"]) + 1

    def test_persistent_worker_error_quarantines_not_crashes(self):
        """A fault that survives retry + bisection on every subset is
        the device path's, not a document's: the requests fail with the
        raw error, nothing is quarantined, and close() raises it."""
        profiles, d, raw = _workload(n_docs=2)
        stage = _stage(profiles, d)

        def boom(payloads, record=True, epoch=None):
            raise RuntimeError("device fell over")

        stage._filter_bytebatch = boom
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=5,
                         queue_cap=8)
        tickets = [loop.submit(p) for p in raw]
        for t in tickets:
            assert t.done.wait(timeout=60)
        with pytest.raises(RuntimeError, match="device fell over"):
            loop.close()
        for t in tickets:
            assert t.failed and not isinstance(t.error, KernelFault)
            assert "device fell over" in str(t.error)
        s = loop.slo_summary()
        assert s["quarantined"] == 0 and s["failed"] == len(raw)
        assert len(loop.dead_letter) == 0

    def test_worker_error_propagates_on_close_without_recovery(self):
        """``recover=False`` restores the strict contract: a worker
        error fails the affected requests and re-raises at close()."""
        profiles, d, raw = _workload(n_docs=2)
        stage = _stage(profiles, d)

        def boom(payloads, record=True, epoch=None):
            raise RuntimeError("device fell over")

        stage._filter_bytebatch = boom
        loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=5,
                         queue_cap=8, recover=False)
        tickets = [loop.submit(p) for p in raw]
        for t in tickets:
            assert t.done.wait(timeout=60)
        with pytest.raises(RuntimeError, match="device fell over"):
            loop.close()
        assert all(t.failed for t in tickets)
        s = loop.slo_summary()
        assert s["failed"] == len(raw) and s["quarantined"] == 0


# ------------------------------------------------------------ arrival traces
class TestArrivalTraces:
    def test_poisson_seeded_and_monotonic(self):
        a = poisson_arrivals(256, 100.0, seed=7)
        b = poisson_arrivals(256, 100.0, seed=7)
        c = poisson_arrivals(256, 100.0, seed=8)
        assert np.array_equal(a, b) and not np.array_equal(a, c)
        assert (np.diff(a) > 0).all()
        # mean inter-arrival ~ 1/rate (loose 3-sigma-ish bound)
        assert 1 / 100.0 * 0.7 < np.diff(a).mean() < 1 / 100.0 * 1.3

    def test_burst_arrivals_live_in_on_windows(self):
        on_s, off_s = 0.02, 0.08
        a = burst_arrivals(200, 1000.0, on_s=on_s, off_s=off_s, seed=3)
        assert (np.diff(a) > 0).all()
        phase = np.mod(a, on_s + off_s)
        assert (phase <= on_s + 1e-9).all(), "arrival outside ON window"
        assert np.array_equal(
            a, burst_arrivals(200, 1000.0, on_s=on_s, off_s=off_s, seed=3))

    def test_replay_arrivals(self):
        assert np.array_equal(replay_arrivals(4), np.zeros(4))
        r = replay_arrivals(4, 100.0)
        assert np.allclose(np.diff(r), 0.01)

    def test_make_arrivals_dispatch(self):
        assert len(make_arrivals("poisson", 8, rate_hz=50.0)) == 8
        assert len(make_arrivals("burst", 8, rate_hz=500.0)) == 8
        assert len(make_arrivals("replay", 8, rate_hz=50.0)) == 8
        with pytest.raises(ValueError, match="unknown arrival"):
            make_arrivals("fractal", 8, rate_hz=50.0)

    def test_run_trace_under_seeded_burst(self):
        """The CI serve job's scenario in miniature: a seeded bursty
        trace through a bounded loop — terminates, p99 finite, the
        counters account for every arrival."""
        profiles, d, raw = _workload(n_docs=24)
        arrivals = burst_arrivals(len(raw), 2000.0, on_s=0.01,
                                  off_s=0.02, seed=11)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=10, queue_cap=16, max_inflight=2)
        with loop:
            tickets = run_trace(loop, raw, arrivals)
        assert len(tickets) == len(raw)
        s = loop.slo_summary()
        assert s["admitted"] + s["shed"] == len(raw)
        assert s["completed"] == s["admitted"]
        assert np.isfinite(s["p99_ms"])

    def test_run_trace_length_mismatch_raises(self):
        profiles, d, raw = _workload(n_docs=4)
        loop = ServeLoop(_stage(profiles, d), max_batch=BATCH,
                         deadline_ms=10, queue_cap=8)
        with loop:
            with pytest.raises(ValueError, match="payloads"):
                run_trace(loop, raw, np.zeros(3))


# ----------------------------------------- K-deep route_bytes_pipelined
class TestRouteBytesPipelinedKDeep:
    """Regression coverage for the satellite fix: the 2-deep double
    buffer is now the K=2 case of the K-deep machinery, and staging
    (→ ``put_seconds``) happens exactly once per batch at any depth."""

    def _workload2d(self, n_docs=12):
        profiles, d, raw = _workload(n_docs=n_docs, seed=5)
        return profiles, d, raw

    @pytest.mark.parametrize("depth", [1, 2, 3, 8])
    def test_depth_parity_and_single_staging(self, depth):
        profiles, d, raw = self._workload2d()
        stage = _stage(profiles, d, data_shards=2)
        stages_in = []
        orig = stage._stage_in
        stage._stage_in = lambda bufs: (stages_in.append(len(bufs))
                                        or orig(bufs))
        got = _routes(stage.route_bytes_pipelined(iter(raw), depth=depth))
        want = _routes(_stage(profiles, d,
                              data_shards=2).route_bytes(raw))
        assert got == want
        # 12 docs / batch 4 = 3 batches, each staged EXACTLY once —
        # this is the put_seconds single-count regression: staging is
        # where put_seconds accrues, so one staging per batch means one
        # accounting per batch at every depth
        assert stages_in == [BATCH] * 3
        assert stage.stats["batches"] == 3
        # depth 1 is fully synchronous (no overlap); deeper pipelines
        # overlap every batch after the first
        want_overlap = 0 if depth == 1 else 2
        assert stage.stats["overlapped_batches"] == want_overlap

    def test_default_depth_is_double_buffer(self):
        profiles, d, raw = self._workload2d()
        stage = _stage(profiles, d, data_shards=2)
        assert stage.pipeline_depth == 2
        got = _routes(stage.route_bytes_pipelined(raw))
        want = _routes(_stage(profiles, d,
                              data_shards=2).route_bytes(raw))
        assert got == want
        assert stage.stats["overlapped_batches"] == 2

    def test_pipeline_depth_field_threads_through(self):
        profiles, d, raw = self._workload2d()
        stage = _stage(profiles, d, data_shards=2, pipeline_depth=3)
        got = _routes(stage.route_bytes_pipelined(raw))
        want = _routes(_stage(profiles, d,
                              data_shards=2).route_bytes(raw))
        assert got == want
