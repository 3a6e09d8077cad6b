"""Benchmark orchestrator — one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--json [PATH]]``

Sections:
  fig8   — area model, 4 scenarios (paper Fig 8)
  fig9   — filtering throughput vs YFilter baseline (paper Fig 9)
  ingest — ingest_throughput: parse cost end-to-end over the three
           ingestion paths (events / bytes-host / bytes-device — the
           paper's same-chip parser+filter vs host parsing)
  kernel — kernel_vs_scan: the streaming megakernel (bit-packed Pallas
           hot path) vs the lax.scan oracle, events and one-launch
           fused-bytes variants (padded + segment-packed) over a
           (scenario × batch × n_queries) grid; the ``backend`` field
           records compiled (TPU) vs interpret rows, and the pallas
           bytes rows are re-emitted as measured ``bench="roofline"``
           rows (achieved stream bandwidth as % of the HBM ceiling)
  qscale — query_scaling: docs/s as the standing profile set grows
           10²→10⁴, monolithic vs sharded query plans (the paper's
           scalability-in-profiles claim, §3.5)
  docscale — doc_scaling: docs/s over the (batch × data-shard ×
           query-shard) grid, bytes → verdict through the 2-D
           ("data", "model") mesh program (the paper's document-stream
           replication, §3.5 second axis)
  churn  — churn_latency: per-op subscribe/unsubscribe on a sharded
           plan vs a full recompile
  serve  — serve_latency: p50/p99/p999 bytes→verdict latency + shed
           rate of the CONTINUOUS serve loop under seeded Poisson and
           bursty (ON/OFF) arrival traces — the service-level view of
           the paper's "very high input ratios" claim (admission
           control, adaptive batching, K-deep dispatch; see
           repro.serve.loop)
  twig   — twig-pattern filtering cost structure (paper §5 extension)
  roofline — 3-term roofline per (arch × shape) from dry-run artifacts
             (only if launch/dryrun.py results exist; see EXPERIMENTS.md)

Output: JSON-lines to stdout (one row per measurement); ``--json``
additionally writes the rows to a file (default ``BENCH_filtering.json``)
so CI accumulates a perf trajectory.  ``--profile [DIR]`` wraps the
whole bench run (typically paired with ``--only``) in
``jax.profiler.trace`` and prints the trace directory, so a kernel win
is inspectable in the profiler instead of inferred from wall clocks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ALL_SECTIONS = ("fig8", "fig9", "ingest", "kernel", "qscale", "docscale",
                "churn", "serve", "twig", "roofline")


def run_sections(sections, full: bool) -> list[dict]:
    rows: list[dict] = []

    if "fig8" in sections:
        from benchmarks import bench_area
        r = bench_area.run()
        rows += r + bench_area.summarize(r)

    if "fig9" in sections:
        from benchmarks import bench_throughput
        if full:
            rows += bench_throughput.run(n_docs=32, nodes_per_doc=2000)
        else:
            rows += bench_throughput.run(
                query_counts=(16, 64, 256), path_lengths=(2, 4),
                n_docs=8, nodes_per_doc=200)

    if "ingest" in sections:
        from benchmarks import bench_throughput
        if full:
            rows += bench_throughput.run_ingest(n_docs=32,
                                                nodes_per_doc=2000)
        else:
            rows += bench_throughput.run_ingest(
                query_counts=(16, 64), n_docs=8, nodes_per_doc=200)

    if "kernel" in sections:
        from benchmarks import bench_throughput, roofline
        if full:
            kr = bench_throughput.run_kernel_vs_scan(
                query_counts=(64, 256, 1024), batch_sizes=(8, 32),
                nodes_per_doc=400, repeat=3)
        else:
            # acceptance grid: megakernel vs scan, events + fused bytes
            # over both length scenarios (uniform + skewed — the packed
            # rows' events_per_slot win lives on the skewed one);
            # interpret-mode kernel rows are slow by design — small
            # batches keep the section's unrolled-grid cost bounded
            kr = bench_throughput.run_kernel_vs_scan(
                query_counts=(64, 256), batch_sizes=(4,),
                nodes_per_doc=150, repeat=1)
        # measured roofline view of the pallas bytes rows rides along
        rows += kr + roofline.megakernel_rows(kr)

    if "qscale" in sections:
        from benchmarks import bench_throughput
        if full:
            rows += bench_throughput.run_query_scaling(
                n_docs=16, nodes_per_doc=400)
        else:
            # acceptance sweep 10²→10⁵ profiles on a small doc batch;
            # the 10⁵ rows carry the subscription-axis columns
            # (state_compression, verdict_bytes, sparse_exact)
            rows += bench_throughput.run_query_scaling(
                max_queries=100_000, shard_counts=(1, 2, 4),
                n_docs=4, nodes_per_doc=120, repeat=1)

    if "docscale" in sections:
        from benchmarks import bench_throughput
        if full:
            rows += bench_throughput.run_doc_scaling(
                batch_sizes=(16, 64), nodes_per_doc=400)
        else:
            # acceptance grid: docs/s per (batch, data, query) shard
            # point — batches big enough that per-shard work dominates
            # dispatch overhead, so the data-axis slope is visible
            rows += bench_throughput.run_doc_scaling(
                batch_sizes=(16,), data_shard_counts=(1, 2, 4),
                query_shard_counts=(1, 2), n_queries=64,
                nodes_per_doc=200, repeat=2)

    if "churn" in sections:
        from benchmarks import bench_throughput
        rows += bench_throughput.run_churn(
            n_queries=1024 if full else 256,
            n_ops=32 if full else 8)

    if "serve" in sections:
        from benchmarks import bench_serve
        rows += bench_serve.run(full=full)

    if "twig" in sections:
        from benchmarks import bench_twig
        rows += bench_twig.run(n_docs=24 if full else 10,
                               nodes_per_doc=300 if full else 120)

    if "roofline" in sections:
        from benchmarks import roofline
        rows += roofline.rows_from_artifacts()

    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slower)")
    ap.add_argument("--only", default=None,
                    help="run a single section: " + "|".join(ALL_SECTIONS))
    ap.add_argument("--json", nargs="?", const="BENCH_filtering.json",
                    default=None, metavar="PATH",
                    help="also write rows to a JSON file "
                         "(default: BENCH_filtering.json)")
    ap.add_argument("--profile", nargs="?", const="/tmp/repro-bench-trace",
                    default=None, metavar="DIR",
                    help="wrap the bench run in jax.profiler.trace(DIR) "
                         "and print the trace dir (pair with --only to "
                         "profile one section)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    sections = [args.only] if args.only else list(ALL_SECTIONS)

    if args.profile:
        import jax

        with jax.profiler.trace(args.profile):
            rows = run_sections(sections, args.full)
        print(f"# profiler trace written to {args.profile} "
              f"(tensorboard --logdir {args.profile})", file=sys.stderr)
    else:
        rows = run_sections(sections, args.full)

    for r in rows:
        print(json.dumps(r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
