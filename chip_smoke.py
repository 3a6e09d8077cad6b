"""Run the served path once on a TPU and check what comes out.

Raw document bytes → ``FilterStage(engine="streaming", sparse=True)`` →
``ServeLoop`` → the one-launch Pallas megakernel → bounded match lists,
at a deployment's size: 10,000 generated XPath profiles over the 24-tag
DTD (path lengths 2–6, ``p_wild=0.1``, ``p_desc=0.3``) against a stream
of 2,048 documents of 200–4,000 elements each (log-uniform, 3–68 KB),
all made from ``--seed``.  Documents arrive on a seeded Poisson trace;
the loop blocks the producer rather than shed.

Checks: every batch runs on the fused kernel route (``kernel-fused``);
nothing is shed, quarantined or failed; the match lists equal those of
the same engine with ``kernel="scan"`` over the whole stream, and those
of the ``oracle`` engine on a sample of documents.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the four-chip phase: the
                                      # query-sharded and 2-D (data ×
                                      # model) routes against one chip

The last line of standard output is one JSON object; it carries
``"ok": true`` only when every check passed on a TPU.  Off the chip,
or with ``REPRO_PALLAS_INTERPRET`` set, the script exits non-zero
without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_TAGS = 24
PATH_LENGTHS = range(2, 7)
PROFILES_PER_LENGTH = 2000
P_WILD, P_DESC = 0.1, 0.3
MIN_NODES, MAX_NODES = 200, 4000
BATCH = 64
#: one padded row per document: 4,000 elements × 17 wire bytes fit, so
#: every batch of the stream has the same shape (one compile)
ROW_BYTES = 68 * 1024
#: event axis of the scan reference: 2 events per element
EVENT_BUCKET = 8192
ORACLE_SAMPLE = 32
#: arrivals per second of the Poisson trace, above what the chip drains:
#: the queue fills and the producer blocks
RATE_HZ = 4000.0


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def check_platform(chips: int):
    """The run counts only on a TPU, with the kernels compiled."""
    import jax

    devices = jax.devices()
    require(devices[0].platform == "tpu",
            f"JAX runs on {devices[0].platform!r}, not on a TPU")
    from repro.kernels import interpret_default

    require(not interpret_default(),
            "REPRO_PALLAS_INTERPRET asks for the Pallas interpreter")
    require(len(devices) >= chips,
            f"{chips} chips asked for, {len(devices)} present")
    return devices


def workload(seed: int, n_docs: int):
    """Profiles, dictionary and the document stream, made from ``seed``."""
    import numpy as np

    from repro.core.dictionary import TagDictionary
    from repro.core.events import encode_bytes
    from repro.data.filter_stage import TEXT_FILL
    from repro.data.generator import DTD, gen_document, gen_profiles

    dtd = DTD.generate(n_tags=N_TAGS, seed=seed)
    d = TagDictionary()
    dtd.register(d)
    profiles = [q for length in PATH_LENGTHS
                for q in gen_profiles(dtd, n=PROFILES_PER_LENGTH,
                                      length=length, p_wild=P_WILD,
                                      p_desc=P_DESC,
                                      seed=seed * 10 + length)]
    rng = np.random.default_rng(seed)
    nodes = np.exp(rng.uniform(np.log(MIN_NODES), np.log(MAX_NODES),
                               n_docs)).astype(int)
    docs = [gen_document(dtd, target_nodes=int(n), seed=seed * n_docs + i)
            for i, n in enumerate(nodes)]
    payloads = [encode_bytes(x, text_fill=TEXT_FILL) for x in docs]
    require(max(map(len, payloads)) <= ROW_BYTES,
            "a document outgrew the padded row")
    return profiles, d, docs, payloads


def make_stage(profiles, d, match_cap: int, **kw):
    """The served configuration: streaming engine, sparse delivery, one
    batch shape."""
    from repro.data.filter_stage import FilterStage

    return FilterStage(profiles, d, engine="streaming", sparse=True,
                       keep_unmatched=True, batch_size=BATCH,
                       byte_bucket=ROW_BYTES,
                       engine_options={"match_cap": match_cap}, **kw)


def doc_matches(routed) -> tuple:
    return tuple(sorted(int(g) for rd in routed
                        for g in rd.matched_profiles))


def serve(stage, payloads, seed: int, label: str,
          route: str = "kernel-fused") -> list[tuple]:
    """Warm the batch shape, then serve the whole stream through the
    loop, every batch on ``route``; returns each document's match list
    in admission order."""
    from repro.serve.loop import ServeLoop, poisson_arrivals, run_trace

    # a lowering or compile error raises here, before the trace; the
    # same batch again, compiled, sets the compile time apart
    t0 = time.perf_counter()
    list(stage.route_bytes(payloads[:BATCH]))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    list(stage.route_bytes(payloads[:BATCH]))
    again = time.perf_counter() - t0
    log(f"{label}: compile {first - again:.3f} s (first batch "
        f"{first:.3f} s, again {again:.3f} s)")
    before = dict(stage.stats["verdict_paths"])
    # batches close on size only, so the stream keeps the warmed shape
    loop = ServeLoop(stage, max_batch=BATCH, deadline_ms=600_000,
                     queue_cap=4 * BATCH, max_inflight=2, overload="block")
    t0 = time.perf_counter()
    with loop:           # close() re-raises any loop error
        tickets = run_trace(loop, payloads,
                            poisson_arrivals(len(payloads), RATE_HZ,
                                             seed=seed))
    wall = time.perf_counter() - t0
    s = loop.slo_summary()
    paths = {k: v - before.get(k, 0)
             for k, v in stage.stats["verdict_paths"].items()
             if v != before.get(k, 0)}
    log(f"{label}: served {s['completed']}/{s['arrived']}, quarantined "
        f"{s['quarantined']}, failed {s['failed']}, shed {s['shed']} in "
        f"{s['batches']} batches, {wall:.3f} s wall; verdict paths {paths}")
    require(s["completed"] == len(payloads) and s["shed"] == 0
            and s["quarantined"] == 0 and s["failed"] == 0,
            f"{label}: not every request was served")
    require(set(paths) == {route},
            f"{label}: batches left the {route} route: {paths}")
    return [doc_matches(t.routed) for t in tickets]


def route_matches(stage, payloads) -> list[tuple]:
    return [doc_matches([rd]) for batch in stage.route_bytes(payloads)
            for rd in batch]


def one_chip(args, profiles, d, docs, payloads, match_cap: int) -> None:
    import numpy as np

    from repro.core import engines
    from repro.core.events import EventBatch
    from repro.data.filter_stage import FilterStage

    stage = make_stage(profiles, d, match_cap)
    eng = stage._eng
    require(eng.kernel_enabled, "the engine did not enable the megakernel")
    meta = eng.plan_.meta
    log(f"states {meta['n_states']}, blocks {meta['n_blocks']} of "
        f"{meta['blk']} states, match cap {match_cap}")
    got = serve(stage, payloads, args.seed, "kernel")

    t0 = time.perf_counter()
    scan = FilterStage(profiles, d, engine="streaming", keep_unmatched=True,
                       batch_size=BATCH, byte_bucket=ROW_BYTES,
                       bucket=EVENT_BUCKET,
                       engine_options={"kernel": "scan"})
    want = route_matches(scan, payloads)
    same = got == want
    log(f"scan reference over {len(want)} documents: "
        f"{time.perf_counter() - t0:.3f} s wall; "
        f"{'match lists equal' if same else 'MATCH LISTS DIFFER'}")
    require(same, "the megakernel's match lists differ from the scan's")

    t0 = time.perf_counter()
    rng = np.random.default_rng(args.seed + 1)
    sample = sorted(int(i) for i in rng.choice(len(docs), ORACLE_SAMPLE,
                                               replace=False))
    oracle = engines.create("oracle", stage.nfa, dictionary=d)
    res = oracle.filter_batch(EventBatch.from_streams(
        [docs[i] for i in sample]))
    ref = [tuple(int(g) for g in np.flatnonzero(res.matched[k]))
           for k in range(len(sample))]
    same = all(got[i] == r for i, r in zip(sample, ref))
    log(f"oracle on {len(sample)} sampled documents: "
        f"{time.perf_counter() - t0:.3f} s wall; "
        f"{'match lists equal' if same else 'MATCH LISTS DIFFER'}")
    require(same, "the megakernel's match lists differ from the oracle's")
    log(f"matches: {sum(map(len, got))} (document, profile) pairs")


def four_chips(args, profiles, d, payloads, match_cap: int) -> None:
    """Query-sharded and 2-D routes, each exact against one chip."""
    base = serve(make_stage(profiles, d, match_cap), payloads, args.seed,
                 "one chip")
    # the 2-D program returns dense verdicts, sparsified on the host
    routes = {"query_shards=4": (dict(query_shards=4), "kernel-fused"),
              "data_shards=2 x model 2": (dict(query_shards=2,
                                               data_shards=2), "dense-2d")}
    for label, (kw, route) in routes.items():
        stage = make_stage(profiles, d, match_cap, **kw)
        log(f"{label}: mesh {dict(stage.mesh.shape)}")
        got = serve(stage, payloads, args.seed, label, route)
        same = got == base
        log(f"{label}: {'match lists equal' if same else 'MATCH LISTS DIFFER'}"
            " to one chip")
        require(same, f"{label} differs from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs", type=int, default=2048,
                    help=f"documents in the stream (a multiple of {BATCH})")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)
    try:
        require(args.docs > 0 and args.docs % BATCH == 0,
                f"--docs must be a positive multiple of {BATCH}")
        devices = check_platform(args.chips)
        from repro.launch.compile_cache import enable_compile_cache

        log(f"device {devices[0].device_kind} x {len(devices)}; compile "
            f"cache {enable_compile_cache()}")
        import numpy as np

        from repro.core.nfa import compile_queries

        t0 = time.perf_counter()
        profiles, d, docs, payloads = workload(args.seed, args.docs)
        nfa = compile_queries(profiles, d, shared=True)
        # every (document, accept state) pair fits: the buffer never
        # overflows into the dense re-run
        match_cap = BATCH * int(np.unique(nfa.tables.accept_state).size)
        log(f"profiles {len(profiles)} ({len(set(map(str, profiles)))} "
            f"distinct), states {nfa.n_states}; documents {len(payloads)}, "
            f"{sum(map(len, payloads))} bytes; workload "
            f"{time.perf_counter() - t0:.3f} s")
        if args.chips == 4:
            four_chips(args, profiles, d, payloads, match_cap)
        else:
            one_chip(args, profiles, d, docs, payloads, match_cap)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
