"""A tiny checkout for CPU tests: the real harness, metrics and reference
over the XMark deployment at a tiny scale, with fewer profiles and short
mixes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = dict(
    json.loads((BENCH / "configs" / "xmark-1k.json").read_text()),
    name="tiny", scale_factor=0.0001,
    profiles={"lengths": [4, 5, 6], "per_length": [60, 60, 60],
              "p_wild": 0.1, "p_desc": 0.3, "seed_base": 0},
    stage={"engine": "streaming", "sparse": True, "keep_unmatched": True,
           "byte_bucket": 8192})
CONFIG["documents"] = dict(CONFIG["documents"], text_bytes=8, pool=8)
MIXES = {
    "backlog": {"arrivals": {"kind": "backlog"}, "warm_batches": 2,
                "loop": {"max_batch": 4, "deadline_ms": 600000,
                         "max_inflight": 2, "queue_cap": 16,
                         "overload": "block"}},
    "poisson": {"arrivals": {"kind": "poisson", "rate_hz": 10}, "warm_s": 0.5,
                "loop": {"max_batch": 2, "deadline_ms": 20,
                         "max_inflight": 2, "queue_cap": 16,
                         "overload": "shed"}},
    "dp4": {"arrivals": {"kind": "backlog"}, "warm_batches": 2,
            "loop": {"max_batch": 8, "deadline_ms": 600000,
                     "max_inflight": 2, "queue_cap": 32,
                     "overload": "block"}},
}
#: chips per mix; the others take one
CHIPS = {"dp4": 4}


def make_root(tmp: Path) -> Path:
    """A checkout holding ``BENCHMARK.json`` with cells ``tiny.backlog``,
    ``tiny.poisson`` and ``tiny.dp4`` (four chips), the real metric
    readers and reference."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b = tmp / "bench"
    shutil.copytree(BENCH / "metrics", b / "metrics")
    (b / "configs").mkdir()
    shutil.copy(BENCH / "configs" / "linear_xpath.py", b / "configs")
    shutil.copy(BENCH / "peaks.json", b / "peaks.json")
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (b / "traffic").mkdir()
    cells = []
    for mix, body in MIXES.items():
        (b / "traffic" / f"tiny-{mix}.json").write_text(json.dumps(body))
        cells.append({"name": f"tiny.{mix}", "config": "tiny",
                      "traffic": f"tiny-{mix}", "chips": CHIPS.get(mix, 1),
                      "why": "test"})
    names = [c["name"] for c in cells]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n in names
                              if n.endswith(m["name"].split(".")[-1])
                              or any(w.endswith("." + n.split(".")[1])
                                     for w in m["workloads"])]
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = cells
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
