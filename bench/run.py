"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is the run's result as one JSON object; the numbers that
decide ``correct`` are the last lines of standard error.  Off a TPU, or
with ``REPRO_PALLAS_INTERPRET`` set, the run exits non-zero and prints no
result.
"""
import time

T_PROCESS = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], t_process=T_PROCESS))
