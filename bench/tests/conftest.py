import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU; none of them loads the TPU library
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
