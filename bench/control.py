"""The control: what ``correct`` must refuse.

The configurations state exact match lists.  The control is the plain
reference with that guarantee broken the way a tempting kernel shortcut
would break it, every child step (``/``) evaluated as a descendant step
(``//``), dropping the parent test, put in the program's place: the
stage's bytes-to-verdict call returns the control's match lists, and the
rest of the run (loop, fan-out, delivery, the comparison) is unchanged.
Every run with it must come out ``correct: false``.

    python3 bench/control.py --workload xmark-1k.backlog --seconds 10 \\
        --seeds 11 12 13

runs the cell once per seed in this process, on the chip, at the cell's
own size and load, and prints each run's compared numbers.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

T_PROCESS = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]


def install(reference: Path):
    """Put the control, built from the reference module at ``reference``,
    in place of ``FilterStage._filter_bytebatch``; returns a function
    that puts the program back."""
    import numpy as np

    from bench.harness import load_module
    from repro.core.engines import SparseResult
    from repro.data.filter_stage import FilterStage

    def control_batch(stage, bufs, record=True, epoch=None):
        ctl = getattr(stage, "_bench_control", None)
        if ctl is None:
            mod = load_module(reference)
            ctl = stage._bench_control = mod.Reference(
                [q.raw for q in stage.profiles],
                list(stage.dictionary.id_to_tag), relax_child=True)
            stage._bench_decode = mod.decode
        t0 = time.perf_counter()
        docs, ids = [], []
        for i, b in enumerate(bufs):
            m = ctl.match(*stage._bench_decode(b))
            docs.append(np.full(m.size, i, np.int32))
            ids.append(m)
        docs, ids = np.concatenate(docs), np.concatenate(ids)
        res = SparseResult(docs, ids, np.zeros_like(ids), len(bufs),
                           len(stage.profiles), meta={"path": "control"})
        if record:
            stage._record(res, len(bufs), sum(map(len, bufs)),
                          time.perf_counter() - t0)
        return res

    original = FilterStage._filter_bytebatch
    FilterStage._filter_bytebatch = control_batch

    def restore():
        FilterStage._filter_bytebatch = original

    return restore


def main(argv=None) -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.CellSpec.load(args.workload)
    install(ROOT / "bench" / "configs" / f"{cell.config['reference']}.py")
    for seed in args.seeds:
        harness.main(["--workload", args.workload, "--seed", str(seed),
                      "--seconds", str(args.seconds), "--trace", "0"],
                     t_process=T_PROCESS)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
