"""Planted faults in the bytes megakernel's walk: what ``correct`` must
refuse at a cell's own size.

The control (``bench/control.py``) breaks the guarantee in the
reference's semantics; these break the walk that streams each row from
HBM, the ways a change to its staging would:

- ``lost-tail``: every walk stops at nine tenths of its row, so a
  document's last tenth is never filtered;
- ``stack-reset``: the tag stack is emptied at every chunk, so no
  element open across a chunk edge is seen as an ancestor.

The planted kernel is a copy of ``repro.kernels.stream_filter`` with one
line added, put in the streaming engine's place; the rest of the run
(loop, fan-out, delivery, the comparison) is the program's.

    python3 bench/planted.py --workload xmark-10m.poisson \\
        --fault lost-tail --seconds 10 --seeds 11 12

runs the cell once per seed in this process, on the chip; every result
line must say ``correct: false``.
"""
from __future__ import annotations

import argparse
import importlib.util
import sys
import time
from pathlib import Path

T_PROCESS = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]

#: fault → (line of the kernel module it follows, line it adds)
FAULTS = {
    "lost-tail": ("    n_chunks = (n_bytes + chunk - 1) // chunk\n",
                  "    n_chunks = (n_bytes * 9 // 10 + chunk - 1) // chunk\n"),
    "stack-reset": ("            cp.wait()\n",
                    "        _reset_stack(stack_ref, init_row)\n"),
}


def planted_kernels(fault: str):
    """``repro.kernels.stream_filter`` with ``fault`` planted."""
    from repro.kernels import stream_filter as sf

    anchor, line = FAULTS[fault]
    src = Path(sf.__file__).read_text()
    if src.count(anchor) != 1:
        raise RuntimeError(f"{fault}: the kernel's walk has changed")
    spec = importlib.util.spec_from_loader(
        "repro.kernels._planted_stream_filter", loader=None)
    mod = importlib.util.module_from_spec(spec)
    mod.__package__ = "repro.kernels"
    exec(compile(src.replace(anchor, anchor + line), sf.__file__, "exec"),
         mod.__dict__)
    return mod


def install(fault: str):
    """Put the planted kernels in the streaming engine's place; returns
    a function that puts them back.  Both drop every traced program, so
    no launch runs a kernel traced from the other module."""
    import jax

    from repro.core.engines import streaming

    original = streaming.sf
    streaming.sf = planted_kernels(fault)
    jax.clear_caches()

    def restore():
        streaming.sf = original
        jax.clear_caches()

    return restore


def main(argv=None) -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    install(args.fault)
    for seed in args.seeds:
        harness.main(["--workload", args.workload, "--seed", str(seed),
                      "--seconds", str(args.seconds), "--trace", "0"],
                     t_process=T_PROCESS)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
