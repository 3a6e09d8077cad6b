"""Compare the first-match events of a cell's stage, on the chip, with
the ``oracle`` engine's on the host.

    python3 bench/first_events.py --workload xmark-10m.poisson --seed <n> --trees 4 \
        [--route dense]

The harness compares each delivered match list with the plain
reference.  Where every document matches every profile, as in
``xmark-10m``, a list cannot show an extra match.  Each match's first
event can: the kernel computes it and the stage's sparse result carries
it.  This script builds the cell's pool, plan and stage as a run of the
cell does, filters ``--trees`` salted trees of the pool through the
stage's sparse route in batches of the cell's largest size, and compares
every ``(document, profile, first event)`` row with the ``oracle``
engine's.  ``--route dense`` filters through the dense bytes kernel
instead, the one that ``xmark-1k.dp4`` runs on each of its chips.  The last line of standard output is one JSON object; its
``equal`` is true only when every row agrees.  Off a TPU, or with
``REPRO_PALLAS_INTERPRET`` set, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.traffic import generator  # noqa: E402


def rows(res, n_docs: int) -> list[np.ndarray]:
    """Per document, its ``(profile, first event)`` rows, sorted."""
    out = []
    for k in range(n_docs):
        m = res.doc_ids == k
        r = np.stack([res.query_ids[m], res.first_event[m]], axis=1)
        out.append(r[np.lexsort(r.T[::-1])])
    return out


def oracle_rows(oracle, kinds, tags) -> np.ndarray:
    from repro.core.events import EventBatch, EventStream

    want = oracle.filter_batch(EventBatch.from_streams(
        [EventStream(kinds, tags)]))
    q = np.flatnonzero(want.matched[0])
    return np.stack([q, want.first_event[0, q]], axis=1)


def main(argv=None, *, root: Path = ROOT, check=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trees", type=int, default=4)
    ap.add_argument("--route", choices=("sparse", "dense"),
                    default="sparse")
    args = ap.parse_args(argv)
    spec = harness.CellSpec.load(args.workload, root)
    try:
        devices = (check or harness.check_platform)(spec.chips)
    except harness.NoChip as e:
        harness.log(f"no measurement: {e}")
        return 2
    harness.enable_compile_cache(root)
    from repro.core import engines

    run = harness.Run(spec, args.seed, 0.0, T_PROCESS)
    run.build()
    stage, pool = run.stage, run.pool
    # off the sparse route, the stage's engine returns the dense verdict
    stage.sparse = args.route == "sparse"
    oracle = engines.create("oracle", stage.nfa, dictionary=stage.dictionary)
    rng = generator.rng_for(args.seed, "first-events")
    trees = rng.choice(len(pool), min(args.trees, len(pool)), replace=False)
    compared = rows_equal = 0
    differ = []
    for at in range(0, len(trees), run.max_batch):
        batch = [int(i) for i in trees[at:at + run.max_batch]]
        t0 = time.perf_counter()
        res = stage._filter_bytebatch(
            [pool.salted(i, rng) for i in batch], record=False)
        if not stage.sparse:
            res = res.sparsify()
        t1 = time.perf_counter()
        for i, got in zip(batch, rows(res, len(batch))):
            want = oracle_rows(oracle, pool.kinds[i], pool.tags[i])
            same = got.shape == want.shape and bool((got == want).all())
            compared += 1
            rows_equal += len(want) if same else 0
            if not same:
                differ.append(i)
        harness.log(f"trees {batch}: route {res.meta.get('path')}, stage "
                    f"{t1 - t0:.3f} s, oracle "
                    f"{time.perf_counter() - t1:.3f} s")
    result = {"equal": not differ and compared == len(trees),
              "route": args.route,
              "trees": [int(i) for i in trees], "differ": differ,
              "rows_equal": rows_equal,
              "tree_bytes": [len(pool.templates[int(i)]) for i in trees],
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind}}
    print(json.dumps(result), flush=True)
    return 0 if result["equal"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
