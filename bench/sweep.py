"""Find the knee of an open-loop mix: the highest rate at which nothing is
shed and the backlog does not grow over the window.

    python3 bench/sweep.py --config xmark-1k --traffic poisson-xmark-1k \\
        --rates 40 60 80 100 --seconds 20 --seed 1

Builds and warms the cell once, then drives one window per rate through a
fresh serve loop with the mix's loop settings.  Per rate it prints the
requests due, shed, p50/p95, and the p95 of the requests due in the
second and in the last quarter of the window: a backlog that grows shows
as a last quarter far above the second.  A tool for setting a mix's rate,
not a benchmark run: nothing it prints is a result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = {c["name"]: c for c in spec["configs"]}[args.config]
    cell = harness.CellSpec(
        f"{args.config}.{args.traffic}", 1,
        harness.load_json(ROOT / cfg["file"]),
        harness.load_json(ROOT / "bench" / "traffic"
                          / f"{args.traffic}.json"), [], [])
    devices = harness.check_platform(1)
    harness.enable_compile_cache(ROOT)
    base = harness.Run(cell, args.seed, args.seconds, T_PROCESS)
    base.build()
    base.warm()
    for rate in args.rates:
        cell.mix["arrivals"]["rate_hz"] = rate
        run = harness.Run(cell, args.seed, args.seconds, T_PROCESS)
        for k in ("dep", "pool", "stage", "max_batch", "n_distinct",
                  "stream"):
            setattr(run, k, getattr(base, k))
        run.drive(harness.Tracer(False, run.clock))
        ctx, _ = run.collect()
        lat = ctx.latencies_ms
        q = np.array_split(lat, 4)
        s = run.summary
        print(json.dumps({
            "rate_hz": rate, "due": int(lat.size), "shed": s["shed"],
            "failed": s["failed"], "batches": s["batches"],
            "deadline_closes": s["deadline_closes"],
            "batch_fill": s["batch_fill"],
            "p50_ms": harness.nearest_rank(lat, 50),
            "p95_ms": harness.nearest_rank(lat, 95),
            "p95_q2_ms": harness.nearest_rank(q[1], 95),
            "p95_q4_ms": harness.nearest_rank(q[3], 95),
            "late_p99_ms": harness.nearest_rank(run.late_ms, 99),
            "device": devices[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
