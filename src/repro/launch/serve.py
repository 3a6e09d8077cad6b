"""Serving driver with pub-sub request routing — the paper's use case,
end to end.

Requests carry XML payloads; standing profiles (subscriptions) route each
request to a model replica (the paper's "deliver to interested
subscribers"), then the selected replica generates a response with the
batched serve engine.  The filter runs the TPU levelwise engine — on a
real deployment this sits on the same chips as the model, the paper's
"parser and filter on the same chip eliminates communication" argument.

``--ingest bytes`` serves *raw wire bytes*: payloads arrive as
paper-format byte strings and are parsed on device
(``FilterStage.route_bytes``), so routing runs bytes → verdict with no
per-event host Python — the full same-chip dataflow.  ``--ingest
events`` is the pre-parsed host path.

``--data-shards N`` turns on the second scaling axis: the stage builds
a 2-D ``("data", "model")`` mesh, documents are fanned over the
``"data"`` axis while each device keeps its slice of the subscription
set, and byte ingest runs the async K-deep pipelined serve loop
(``FilterStage.route_bytes_pipelined``: the ``device_put`` of the next
batches overlaps the filter step on batch k).

``--arrival {poisson,burst,replay}`` switches the routing step from the
fixed-request-list driver to the *continuous* serve loop
(:class:`repro.serve.loop.ServeLoop`): requests are submitted on a
seeded arrival trace, admitted against a bounded queue
(``--queue-cap``, ``--overload shed|block``), batched adaptively
(``--batch`` size or ``--deadline-ms``, whichever fires first), run up
to ``--max-inflight`` batches deep, and delivered in order — then the
SLO summary (p50/p99/p999 bytes→verdict latency, shed rate, batch fill,
backpressure waits) is printed and optionally written to
``--latency-json`` with the full latency histogram.

Usage::

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 32 --replicas 2 --ingest bytes --query-shards 2 \
      --data-shards 2
  PYTHONPATH=src python -m repro.launch.serve --requests 64 \
      --arrival burst --rate 800 --deadline-ms 10 --max-inflight 4 \
      --queue-cap 32 --latency-json serve_latency.json
"""
import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core import engines
from repro.core.dictionary import TagDictionary
from repro.core.events import encode_bytes
from repro.data.filter_stage import TEXT_FILL, FilterStage
from repro.data.generator import DTD, gen_corpus, gen_profiles
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve.engine import ServeEngine
from repro.serve.loop import OVERLOAD_POLICIES, ServeLoop, make_arrivals, run_trace


def build_stage(n_replicas: int, *, engine: str = "levelwise",
                batch_size: int = 8, query_shards: int = 1,
                data_shards: int = 1, seed: int = 0,
                plan_cache: str | None = None):
    """The serving driver's pub-sub routing layer, as a reusable piece.

    Deterministic for a given ``seed`` (the CLI smoke tests rebuild it
    to assert routed-output parity against ``main``'s printed queues).
    Returns ``(stage, dtd)`` — the workload generator is needed again
    for payloads and churn profiles.  ``plan_cache`` points the engine
    at a persistent :class:`~repro.checkpoint.PlanCache` directory so a
    restart skips plan recompilation (cold-start recovery).
    """
    dtd = DTD.generate(n_tags=24, seed=seed)
    d = TagDictionary()
    dtd.register(d)
    profiles = gen_profiles(dtd, n=32, length=3, seed=seed)
    opts = {"plan_cache": plan_cache} if plan_cache else {}
    # the stage builds its own ("data", "model") mesh when sharded
    stage = FilterStage(profiles, d, n_shards=n_replicas, engine=engine,
                        keep_unmatched=True, batch_size=batch_size,
                        query_shards=query_shards, data_shards=data_shards,
                        engine_options=opts)
    return stage, dtd


def route_requests(stage: FilterStage, payloads, *, ingest: str = "events",
                   raw=None) -> list[list[int]]:
    """Fan requests out to replica queues through the stage.

    ``ingest="bytes"`` routes ``raw`` wire payloads — through the async
    double-buffered loop when the stage has a 2-D data axis, the plain
    device-ingest path otherwise.
    """
    queues: list[list[int]] = [[] for _ in range(stage.n_shards)]
    if ingest == "bytes":
        routed_batches = (stage.route_bytes_pipelined(raw)
                          if stage.data_shards > 1 else
                          stage.route_bytes(raw))
    else:
        routed_batches = stage.route(payloads)
    for routed in routed_batches:
        for r in routed:
            queues[r.shard].append(r.doc_index)
    return queues


def serve_continuous(stage: FilterStage, raw: list[bytes],
                     args) -> tuple[list[list[int]], dict]:
    """Drive the continuous serve loop over a seeded arrival trace.

    Returns ``(queues, slo)`` — per-replica delivery queues (identical
    to what the batch driver routes when nothing is shed, the loop's
    semantics-vs-schedule contract) and the SLO summary dict.
    """
    deliveries: list = []
    arrivals = make_arrivals(args.arrival, len(raw), rate_hz=args.rate,
                             seed=args.seed)
    loop = ServeLoop(stage, max_batch=args.batch,
                     deadline_ms=args.deadline_ms,
                     queue_cap=args.queue_cap,
                     max_inflight=args.max_inflight,
                     overload=args.overload,
                     deliver=deliveries.append)
    with loop:
        run_trace(loop, raw, arrivals)
    slo = loop.slo_summary()
    queues: list[list[int]] = [[] for _ in range(stage.n_shards)]
    for routed in deliveries:
        for r in routed:
            queues[r.shard].append(r.doc_index)
    if args.latency_json:
        payload = {"arrival": args.arrival, "rate_hz": args.rate,
                   "deadline_ms": args.deadline_ms,
                   "queue_cap": args.queue_cap,
                   "max_inflight": args.max_inflight,
                   "overload": args.overload, "slo": slo,
                   "swaps": loop.swap_summary(),
                   "dead_letter": [
                       {"seq": r["seq"], "error": r["error"],
                        "message": r["message"]}
                       for r in loop.dead_letter],
                   "histogram": loop.latency_histogram(),
                   "latencies_ms": loop.latencies_ms().tolist()}
        with open(args.latency_json, "w") as f:
            json.dump(payload, f, indent=1)
    return queues, slo


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--filter-engine", default="levelwise",
                    choices=list(engines.names()),
                    help="pub-sub routing engine (any registered engine)")
    ap.add_argument("--ingest", default="events",
                    choices=("events", "bytes"),
                    help="request payload form: pre-parsed event streams "
                         "(host parse) or raw wire bytes parsed on device")
    ap.add_argument("--query-shards", type=int, default=1,
                    help="partition the subscription set into this many "
                         "parts run as one stacked program over the mesh "
                         "'model' axis (1 = monolithic plan)")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="fan the document stream over this many mesh "
                         "'data' replicas (2-D data × model program with "
                         "the async K-deep pipelined byte-ingest loop; "
                         "shrinks to what the host can place)")
    ap.add_argument("--arrival", default=None,
                    choices=("poisson", "burst", "replay"),
                    help="serve CONTINUOUSLY: submit requests on this "
                         "seeded arrival trace through the admission-"
                         "controlled serve loop and print the SLO "
                         "summary (default: the batch driver)")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="arrival rate in req/s (burst: the ON-window "
                         "rate; mean is a quarter of it)")
    ap.add_argument("--deadline-ms", type=float, default=10.0,
                    help="adaptive batching: close a batch this long "
                         "after it opens even if under --batch size")
    ap.add_argument("--max-inflight", type=int, default=2,
                    help="K-deep pipelining: dispatched-but-undelivered "
                         "batches held in flight (2 = double buffer)")
    ap.add_argument("--queue-cap", type=int, default=64,
                    help="admission control: bound on the ingest queue; "
                         "arrivals beyond it are shed or block")
    ap.add_argument("--overload", default="shed",
                    choices=OVERLOAD_POLICIES,
                    help="overload policy at --queue-cap: shed the "
                         "arrival or block the producer")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-trace seed (workload seeds are fixed)")
    ap.add_argument("--latency-json", default=None, metavar="PATH",
                    help="write the SLO summary + latency histogram "
                         "JSON here (the CI serve job's artifact)")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persistent compiled-plan cache directory: "
                         "restarts with the same subscription set skip "
                         "plan recompilation (crash-recovery cold start)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced).with_(vocab=256)
    params = T.init_model(cfg, jax.random.PRNGKey(0))
    replica_engines = [ServeEngine(cfg, params, batch=args.batch,
                                   max_len=args.prompt_len + args.gen_len + 4)
                       for _ in range(args.replicas)]

    # pub-sub routing layer: profiles → replicas
    stage, dtd = build_stage(args.replicas, engine=args.filter_engine,
                             batch_size=args.batch,
                             query_shards=args.query_shards,
                             data_shards=args.data_shards,
                             plan_cache=args.plan_cache)
    payloads = gen_corpus(dtd, n_docs=args.requests, nodes_per_doc=60,
                          seed=1)

    # serialization is request *arrival* (real deployments receive bytes),
    # so it happens outside the routing timer; the continuous loop is
    # always a bytes service — wire payloads are what arrives
    raw = ([encode_bytes(doc, text_fill=TEXT_FILL) for doc in payloads]
           if args.ingest == "bytes" or args.arrival else None)
    t0 = time.perf_counter()
    if args.arrival:
        queues, slo = serve_continuous(stage, raw, args)
        ingest_label = f"bytes, {args.arrival} arrivals"
    else:
        queues = route_requests(stage, payloads, ingest=args.ingest, raw=raw)
        slo = None
        ingest_label = f"{args.ingest} ingest"
    t_route = time.perf_counter() - t0
    tp = stage.throughput()
    print(f"[serve] routed {args.requests} requests ({ingest_label}) → "
          f"{[len(q) for q in queues]} per replica ({t_route*1e3:.1f} ms; "
          f"{tp['engine']}×{tp['query_shards']}: "
          f"{tp['docs_per_s']:.0f} docs/s, {tp['mb_per_s']:.2f} MB/s)")
    if slo is not None:
        print(f"[serve] SLO bytes→verdict: p50 {slo['p50_ms']:.2f} ms, "
              f"p99 {slo['p99_ms']:.2f} ms, p999 {slo['p999_ms']:.2f} ms "
              f"({slo['completed']}/{slo['arrived']} served at "
              f"{slo['served_per_s']:.0f}/s, shed {slo['shed']} = "
              f"{slo['shed_rate']:.1%})")
        if slo.get("quarantined") or slo.get("failed"):
            print(f"[serve] faults: {slo['quarantined']} quarantined "
                  f"({slo['rejected']} pre-admission), "
                  f"{slo['failed']} failed, {slo['retries']} retries, "
                  f"dead-letter depth {slo['dead_letter_depth']}")
        print(f"[serve] loop: {slo['batches']} batches "
              f"(fill {slo['batch_fill']:.2f}; {slo['size_closes']} size / "
              f"{slo['deadline_closes']} deadline / "
              f"{slo['flush_closes']} flush closes), max queue depth "
              f"{slo['max_queue_depth']}/{args.queue_cap}, "
              f"{slo['backpressure_waits']} backpressure waits at "
              f"K={args.max_inflight}")
    if args.data_shards > 1:
        print(f"[serve] 2-D mesh data×model = "
              f"{tp['mesh_data']}×{tp['mesh_model']}: "
              f"{tp['docs_per_s_per_data_shard']:.0f} docs/s per data "
              f"shard, {tp['queries_per_model_shard']} queries per model "
              f"shard, {tp['overlapped_batches']} overlapped transfers "
              f"({tp['put_s']*1e3:.1f} ms staging)")

    # live subscription churn — the defining pub-sub operation, served
    # without stopping the stream: sharded stages recompile only one
    # partition per op (O(n_queries / query_shards) steady state)
    churn = gen_profiles(dtd, n=4, length=3, seed=99)
    t0 = time.perf_counter()
    gids = [stage.subscribe(q) for q in churn]
    t_sub = time.perf_counter() - t0
    t0 = time.perf_counter()
    for gid in gids[:2]:
        stage.unsubscribe(gid)
    t_unsub = time.perf_counter() - t0
    re_routed = sum(len(r) for r in stage.route(payloads[:args.batch]))
    print(f"[serve] live churn: +{len(gids)} subscriptions "
          f"({t_sub/len(gids)*1e3:.1f} ms/op), -2 "
          f"({t_unsub/2*1e3:.1f} ms/op); re-routed {args.batch} requests "
          f"→ {re_routed} deliveries under the updated subscription set")

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    n_tok = 0
    for rep, queue in enumerate(queues):
        for i in range(0, len(queue), args.batch):
            chunk = queue[i:i + args.batch]
            pad = args.batch - len(chunk)
            prompts = rng.integers(
                0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)
            out = replica_engines[rep].generate({"tokens": prompts},
                                                args.gen_len)
            n_tok += out.shape[1] * (len(chunk))
            del pad
    dt = time.perf_counter() - t0
    print(f"[serve] generated {n_tok} tokens across {args.replicas} "
          f"replicas in {dt:.2f}s ({n_tok/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
