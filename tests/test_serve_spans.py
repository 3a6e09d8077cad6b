"""Spans and counters of the served path (:mod:`repro.core.spans`).

One traced loop run over a sparse stage on the megakernel (the Pallas
interpreter on the CPU): two batches close on size, one on its deadline
and one on the flush at exit, behind a slow consumer and one in-flight
slot.  The counters must add up to the tickets' own stamps, the worker's
spans must fit inside the stage's time, and the profiler's trace must
hold every span on ``/host:CPU`` with the batch it belongs to.
"""
import glob
import os
import time

import jax
import pytest

from repro.core.dictionary import TagDictionary
from repro.core.events import encode_bytes
from repro.data.filter_stage import SPAN_KEYS, TEXT_FILL, FilterStage
from repro.data.generator import DTD, gen_corpus, gen_profiles
from repro.serve.loop import ServeLoop

BATCH = 4
#: a row width no other test uses, so the jit cache holds none of this
#: file's programs when it starts
BYTE_BUCKET = 1536
DEADLINE_MS = 300
SLOW_S = 0.05
WORKER_SPANS = ("xf.pack", "xf.launch", "xf.device", "xf.expand")
BATCH_SPANS = WORKER_SPANS + ("xf.wait_fill", "xf.fan_out", "xf.deliver")


def _wait(tickets):
    for t in tickets:
        assert t.done.wait(timeout=120), "verdict never arrived"


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    dtd = DTD.generate(n_tags=24, seed=3)
    d = TagDictionary()
    dtd.register(d)
    profiles = gen_profiles(dtd, n=12, length=3, seed=3)
    docs = gen_corpus(dtd, n_docs=13, nodes_per_doc=30, seed=4)
    raw = [encode_bytes(x, text_fill=TEXT_FILL) for x in docs]
    stage = FilterStage(profiles, d, engine="streaming", sparse=True,
                        keep_unmatched=True, batch_size=BATCH,
                        byte_bucket=BYTE_BUCKET,
                        engine_options={"kernel": "pallas"})
    # warm the full batch only: the deadline and flush sizes compile
    # inside the loop
    list(stage.route_bytes(raw[:BATCH]))
    stats0 = dict(stage.stats)
    delivered = []

    def deliver(routed):
        time.sleep(SLOW_S)
        delivered.append(({rd.doc_index for rd in routed}, time.monotonic()))

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with ServeLoop(stage, max_batch=BATCH, deadline_ms=DEADLINE_MS,
                       queue_cap=64, max_inflight=1,
                       deliver=deliver) as loop:
            tickets = [loop.submit(p) for p in raw[:2 * BATCH]]
            _wait(tickets)
            more = [loop.submit(p) for p in raw[2 * BATCH:2 * BATCH + 2]]
            _wait(more)
            tickets += more
            # closed at once: the open batch is flushed
            tickets += [loop.submit(p) for p in raw[2 * BATCH + 2:]]
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
    return dict(loop=loop, stage=stage, stats0=stats0, tickets=tickets,
                delivered=delivered, xplane=xplane)


def test_the_run_closes_on_size_deadline_and_flush(run):
    s = run["loop"].slo_summary()
    assert s["size_closes"] >= 1
    assert s["deadline_closes"] >= 1
    assert s["flush_closes"] >= 1
    assert s["completed"] == len(run["tickets"]) == 13


def test_queue_s_sums_admission_to_dispatch(run):
    s = run["loop"].slo_summary()
    want = sum(t.t_dispatch - t.t_submit for t in run["tickets"])
    assert s["queue_s"] == pytest.approx(want, rel=1e-9, abs=1e-12)
    for t in run["tickets"]:
        assert (t.t_submit <= t.t_close <= t.t_dispatch <= t.t_verdict
                <= t.t_delivered)


def test_worker_spans_fit_inside_the_stage_time(run):
    st, st0 = run["stage"].stats, run["stats0"]
    spent = {k: st[k] - st0[k] for k in SPAN_KEYS}
    assert all(v > 0 for v in spent.values()), spent
    assert sum(spent.values()) <= st["seconds"] - st0["seconds"]


def test_stage_counts_the_tag_starts_the_kernel_walked(run):
    """``stats["tag_starts"]``: one per tag the bytes kernel's scalar
    walk visited, so one per event of every payload the loop served."""
    from repro.core.events import _sym_table, decode_tags

    want = sum(len(decode_tags(t.payload, _sym_table())[0])
               for t in run["tickets"])
    st, st0 = run["stage"].stats, run["stats0"]
    assert want > 0
    assert st["tag_starts"] - st0["tag_starts"] == want


def test_loop_spans_are_counted(run):
    s = run["loop"].slo_summary()
    # every batch's delivery sleeps SLOW_S in the consumer
    assert s["deliver_s"] >= s["batches"] * SLOW_S
    assert s["fan_out_s"] > 0
    assert s["wait_fill_s"] >= DEADLINE_MS / 1e3 * 0.9   # the deadline
    assert s["wait_arrival_s"] > 0
    # one slot behind a slow consumer: the batcher waited for it
    assert s["backpressure_waits"] >= 1 and s["wait_slot_s"] > 0


def test_latency_ends_after_delivery(run):
    by_seq = {}
    for seqs, t_cb in run["delivered"]:
        for q in seqs:
            by_seq[q] = t_cb
    for t in run["tickets"]:
        assert t.t_delivered >= by_seq[t.seq]
        assert t.latency_s == t.t_delivered - t.t_submit
        assert t.latency_s >= SLOW_S
    lat = sorted(run["loop"].latencies_ms())
    want = sorted((t.t_delivered - t.t_submit) * 1e3 for t in run["tickets"])
    assert lat == pytest.approx(want)


def test_compiles_count_the_shapes_warm_up_missed(run):
    s = run["loop"].slo_summary()
    # deadline (2) and flush (3) batch sizes were never warmed
    assert s["compiles"] >= 1 and s["compile_s"] > 0


def test_trace_holds_each_batchs_spans_on_the_host(run):
    from jax.profiler import ProfileData

    per_batch: dict[int, list[str]] = {}
    for plane in ProfileData.from_file(run["xplane"]).planes:
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("xf."):
                    continue
                assert plane.name == "/host:CPU", plane.name
                batch = dict(e.stats).get("batch")
                assert isinstance(batch, int), (e.name, e.stats)
                per_batch.setdefault(batch, []).append(e.name)
    n = run["loop"].slo_summary()["batches"]
    assert set(range(n)) <= set(per_batch)
    for b in range(n):
        names = per_batch[b]
        for name in BATCH_SPANS:
            assert name in names, (b, name, names)
        # the worker's spans took the batch's id, once each
        for name in WORKER_SPANS:
            assert names.count(name) == 1, (b, name, names)
    assert "xf.wait_arrival" in per_batch[0]
