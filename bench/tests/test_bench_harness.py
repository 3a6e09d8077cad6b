"""The harness on the CPU: result line, window arithmetic, and that the
comparison refuses the control and the planted faults.  A four-chip cell
runs in a child process on four virtual CPU devices."""
import dataclasses
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import make_root

ROOT = Path(__file__).resolve().parents[2]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


#: a child on four virtual CPU devices runs one cell through the real
#: harness, with a fault named by the test module's function (or the
#: control) planted first: in the stage's fan-out, or where its target
#: says
CHILD = """
import sys
from pathlib import Path

import jax

from bench import control, harness
from bench.tests import test_bench_harness as t
from repro.core.engines.base import FilterEngine
from repro.data.filter_stage import FilterStage

root, cell, seed, seconds, fault = sys.argv[1:6]
if fault == "control":
    control.install(Path(root) / "bench" / "configs" / "linear_xpath.py")
elif fault:
    cls, attr = {"_first_chip_only": (FilterEngine, "filter_bytes_sharded2d")
                 }.get(fault, (FilterStage, "_fan_out"))
    setattr(cls, attr, getattr(t, fault)(getattr(cls, attr)))
sys.exit(harness.main(["--workload", cell, "--seed", seed, "--seconds",
                       seconds, "--trace", "0"], root=Path(root),
                      check=lambda chips: jax.devices()))
"""


def run_child(root, cell, seconds, seed, fault=""):
    """The last line of a four-device child's output, and its errors."""
    path = os.pathsep.join([str(ROOT), str(ROOT / "src"),
                            os.environ.get("PYTHONPATH", "")])
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": path,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(root), cell, seed, seconds,
         fault], env=env, capture_output=True, text=True, timeout=300,
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def run_cell(root, capsys, cell, seconds="1.5", seed="4294967311",
             fault=""):
    import jax

    if harness.CellSpec.load(cell, root).chips > 1:
        return run_child(root, cell, seconds, seed, fault)[0]
    rc = harness.main(["--workload", cell, "--seed", seed, "--seconds",
                       seconds, "--trace", "0"], root=root,
                      check=lambda chips: jax.devices())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.backlog", {"mb_per_s", "setup_s"}),
    ("tiny.poisson", {"p50_ms", "p95_ms", "setup_s"}),
    ("tiny.dp4", {"mb_per_s", "setup_s"})])
def test_result_line_has_the_contracts_keys(root, capsys, cell, metrics):
    res = run_cell(root, capsys, cell)
    assert CONTRACT_KEYS <= set(res) <= CONTRACT_KEYS | {"checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["checks"] == {"wrong_lists": {"value": 0, "limit": 0},
                             "missing": {"value": 0, "limit": 0}}


def _drop_half(orig):
    def fan_out(self, *a, **k):
        return [rd for i, rd in enumerate(orig(self, *a, **k)) if i % 2]
    return fan_out


def _alter_first(orig):
    def fan_out(self, *a, **k):
        out = orig(self, *a, **k)
        m = out[0].matched_profiles
        out[0].matched_profiles = (m[1:] if len(m)
                                   else np.asarray([0], np.int32))
        return out
    return fan_out


def _first_chip_only(orig):
    """The verdicts of the chips past the first left out of the gather."""
    def filter_bytes_sharded2d(self, bb, *a, **k):
        res = orig(self, bb, *a, **k)
        matched = np.array(res.matched)
        matched[-(-bb.batch_size // 4):] = False
        return dataclasses.replace(res, matched=matched)
    return filter_bytes_sharded2d


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.poisson",
                                  "tiny.dp4"])
@pytest.mark.parametrize("fault,check", [(_drop_half, "missing"),
                                         (_alter_first, "wrong_lists")])
def test_planted_faults_are_not_correct(root, capsys, monkeypatch, cell,
                                        fault, check):
    from repro.data.filter_stage import FilterStage

    monkeypatch.setattr(FilterStage, "_fan_out",
                        fault(FilterStage._fan_out))
    res = run_cell(root, capsys, cell, fault=fault.__name__)
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.poisson",
                                  "tiny.dp4"])
def test_control_is_not_correct(root, capsys, cell):
    from bench import control

    restore = control.install(root / "bench" / "configs" / "linear_xpath.py")
    try:
        res = run_cell(root, capsys, cell, fault="control")
    finally:
        restore()
    assert res["correct"] is False
    assert res["checks"]["wrong_lists"]["value"] > 0


def test_four_chip_cell_lays_every_batch_over_the_chips(root):
    """Each batch of ``tiny.dp4`` is split over four replicas of the
    plan on the mesh's ``data`` axis: the stage reports the 2-D route
    for every batch it filtered, and the lists delivered are right."""
    res, err = run_child(root, "tiny.dp4", "1.5", "2147483659")
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4
    routes = [line for line in err.splitlines() if "routes" in line]
    assert len(routes) == 1
    assert routes[0].rsplit("routes ", 1)[1].startswith("{'dense-2d': ")
    assert "'kernel-fused'" not in routes[0]


def test_chips_left_out_of_the_gather_are_not_correct(root):
    res, err = run_child(root, "tiny.dp4", "1.5", "4294967311",
                         "_first_chip_only")
    assert res["correct"] is False
    assert res["checks"]["wrong_lists"]["value"] > 0


def test_one_chip_cell_builds_the_unsharded_stage(root):
    run = harness.Run(harness.CellSpec.load("tiny.backlog", root), 7, 1.0,
                      0.0)
    run.build()
    assert run.stage.data_shards == 1
    assert run.stage.mesh is None and run.stage.sharded_ is None


def test_batch_that_does_not_divide_over_the_chips_is_refused(tmp_path):
    root = make_root(tmp_path)
    mix = root / "bench" / "traffic" / "tiny-dp4.json"
    body = json.loads(mix.read_text())
    body["loop"]["max_batch"] = 6
    mix.write_text(json.dumps(body))
    with pytest.raises(SystemExit, match="does not divide over 4 chips"):
        harness.CellSpec.load("tiny.dp4", root)
    assert harness.CellSpec.load("tiny.backlog", root).chips == 1


def test_route_kernels():
    cfg = {"kernel": "stream_filter_bytes_pallas_sparse"}
    assert harness.route_kernels(cfg, {"kernel-fused": 9}) == [
        "stream_filter_bytes_pallas_sparse"]
    assert harness.route_kernels(cfg, {"dense-2d": 9}) == [
        "stream_filter_bytes_pallas"]


class _Ticket:
    def __init__(self, seq, payload=b"x" * 10, shed=False):
        self.seq, self.payload, self.shed, self.error = seq, payload, shed, None


class _Doc:
    def __init__(self, seq):
        self.doc_index, self.matched_profiles = seq, np.zeros(0, np.int32)


def _bare_run(kind, seconds=1.0):
    spec = harness.CellSpec("c", 1, {}, {"arrivals": {"kind": kind}},
                            [], [])
    run = harness.Run(spec, 1, seconds, 0.0)
    run.edge0, run.edge1, run.t_setup = {}, {}, 1.0
    run.dep = type("D", (), {"profiles": []})()
    return run


def test_mb_per_s_counts_only_deliveries_inside_the_window():
    run = _bare_run("backlog")
    run.tickets = [_Ticket(i) for i in range(8)]
    # batches delivered at t = 1 (window opens), 2, 3, 4 (window closes),
    # 5 (after): only the batches at 2, 3 and 4 count
    run.deliveries = [harness.Delivery(t, [_Doc(2 * k), _Doc(2 * k + 1)])
                      for k, t in enumerate([1.0, 2.0, 3.0, 4.0])]
    run.t_window, run.due = (1.0, 4.0), None
    ctx, rec = run.collect()
    assert ctx.window_docs == 6 and ctx.window_bytes == 60
    assert ctx.window_s == 3.0
    mod = harness.load_module(ROOT / "bench" / "metrics" / "mb_per_s.py")
    assert mod.read(ctx) == pytest.approx(60 / 1e6 / 3.0)


def test_latency_counts_shed_and_undelivered_at_the_cutoff():
    run = _bare_run("poisson")
    run.tickets = [_Ticket(0), _Ticket(1), _Ticket(-1, shed=True),
                   _Ticket(2)]
    run.deliveries = [harness.Delivery(10.1, [_Doc(0)]),
                      harness.Delivery(10.3, [_Doc(1)])]
    run.due = np.asarray([10.0, 10.1, 10.2, 10.3])
    run.t_window, run.cutoff = (10.0, 11.0), 71.0
    ctx, _ = run.collect()
    assert ctx.latencies_ms == pytest.approx([100, 200, 60800, 60700])
    p50 = harness.load_module(ROOT / "bench" / "metrics" / "p50_ms.py")
    assert p50.read(ctx) == pytest.approx(200)
    assert harness.nearest_rank(ctx.latencies_ms, 95) == pytest.approx(60800)


def test_nearest_rank():
    xs = np.arange(1, 101, dtype=float)
    assert harness.nearest_rank(xs, 50) == 50
    assert harness.nearest_rank(xs, 95) == 95
    assert harness.nearest_rank(np.append(xs[:99], math.inf), 100) \
        == math.inf


def test_unknown_device_kind_raises():
    harness.peaks_for("TPU v5 lite", ROOT)
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99", ROOT)


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"REPRO_PALLAS_INTERPRET": "1"}])
def test_off_the_chip_exits_nonzero_without_a_result(env):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "xmark-1k.backlog", "--seed", "1", "--seconds", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
