"""The ``xmark-10m`` configuration and its cell: its files load through
the harness, its trees fit the row, the reader of the kernel's time per
streamed chunk, the first-event comparison script on the CPU, and the
planted kernel faults that a run at the cell's size must refuse."""
import json
from pathlib import Path

import jax
import pytest

from bench import first_events, harness, planted
from bench.harness import CellSpec, Context, reader_path
from bench.tests.tiny import make_root
from bench.traffic import generator

ROOT = Path(__file__).resolve().parents[2]
CELL = "xmark-10m.poisson"


def test_the_cell_loads_with_its_traffic():
    spec = CellSpec.load(CELL, ROOT)
    cfg = spec.config
    assert cfg["name"] == "xmark-10m"
    assert cfg["scale_factor"] == 0.1
    assert cfg["documents"]["pool"] == 16
    assert cfg["stage"]["byte_bucket"] == 11_010_048
    assert cfg["reduced"] == []
    assert spec.chips == 1
    assert spec.mix["arrivals"] == {"kind": "poisson", "rate_hz": 2}
    assert spec.mix["loop"] == {"max_batch": 4, "deadline_ms": 20,
                                "max_inflight": 2, "queue_cap": 64,
                                "overload": "shed"}
    assert spec.mix["warm_s"] == 3
    # p95_ms is left out: at 2/s a 51 s window holds about 102 requests,
    # and their 95th percentile spreads by more than half its bound
    assert {m["name"] for m in spec.end_to_end} == {"p50_ms", "setup_s"}
    assert {m["name"] for m in spec.per_layer} == {
        "kernel_us_per_chunk.10m", "stream_filter_roofline.10m",
        "queue_ms.10m", "engine_host_ms_per_batch.10m",
        "fan_out_ms_per_batch.10m", "device_idle_pct.10m"}
    assert {m["moves"] for m in spec.per_layer} == {"p50_ms"}


def test_the_configuration_differs_from_xmark_1k_only_in_scale():
    one = json.loads((ROOT / "bench/configs/xmark-1k.json").read_text())
    ten = json.loads((ROOT / "bench/configs/xmark-10m.json").read_text())
    for key in ("dtd", "profiles", "guarantees", "kernel", "reference"):
        assert ten[key] == one[key], key
    assert {k: v for k, v in ten["documents"].items() if k != "pool"} \
        == {k: v for k, v in one["documents"].items() if k != "pool"}
    assert {k: v for k, v in ten["stage"].items() if k != "byte_bucket"} \
        == {k: v for k, v in one["stage"].items() if k != "byte_bucket"}


def test_the_largest_tree_of_a_pool_fits_the_row():
    cfg = CellSpec.load(CELL, ROOT).config
    dep = generator.deployment(cfg)
    # a pool's trees are drawn in order from one generator: these are
    # the first four of the cell's pool at this seed
    pool = generator.Pool.build(dep, dict(cfg["documents"], pool=4),
                                seed=3_000_000_007)
    row = cfg["stage"]["byte_bucket"]
    assert 9_000_000 < pool.max_bytes < row
    assert row % 4096 == 0


# ---------------------------------------------- kernel_us_per_chunk reader
def _ctx(trace, chunks=(10, 2_698), batches=(3, 5)):
    def stage(i):
        s = {"batches": batches[i], "docs": 2 * batches[i]}
        if chunks is not None:
            s["stream_chunks"] = chunks[i]
        return s

    return Context("poisson", 1.0, 51.0, edge0={"stage": stage(0)},
                   edge1={"stage": stage(1)}, trace=trace)


def _read(ctx):
    return harness.load_module(
        reader_path("kernel_us_per_chunk.10m", ROOT)).read(ctx)


def test_kernel_us_per_chunk_is_kernel_time_over_the_traced_chunks():
    # 2,688 chunks over 2 batches: 1,344 per launch, 4 launches traced
    trace = {"kernel_s": 0.1183, "kernel_launches": 4}
    assert _read(_ctx(trace)) == pytest.approx(1e6 * 0.1183 / (4 * 1344))


@pytest.mark.parametrize("trace,kw", [
    (None, {}),                                        # untraced run
    ({"kernel_s": 0.1, "kernel_launches": 4}, {"chunks": None}),  # parent
    ({"kernel_s": 0.1, "kernel_launches": 4}, {"chunks": (7, 7)}),
    ({"kernel_s": 0.1, "kernel_launches": 4}, {"batches": (5, 5)}),
    ({"kernel_s": 0.0, "kernel_launches": 0}, {}),
])
def test_kernel_us_per_chunk_reads_nothing_without_a_trace_or_chunks(
        trace, kw):
    assert _read(_ctx(trace, **kw)) is None


# ------------------------------------------------------ first-event check
@pytest.mark.parametrize("route", ["sparse", "dense"])
def test_first_events_agree_with_the_oracle_on_the_cpu(tmp_path, capsys,
                                                       route):
    root = make_root(tmp_path)
    rc = first_events.main(["--workload", "tiny.poisson", "--seed",
                            "3000000011", "--trees", "5", "--route", route],
                           root=root, check=lambda chips: jax.devices())
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["equal"] and len(out["trees"]) == 5
    assert out["rows_equal"] > 0 and out["route"] == route


def test_first_events_refuse_to_run_off_the_chip(tmp_path):
    root = make_root(tmp_path)
    assert first_events.main(["--workload", "tiny.poisson", "--seed", "1"],
                             root=root) == 2


def test_first_events_see_a_first_match_one_event_late(tmp_path, capsys,
                                                       monkeypatch):
    """Same lists, one first event moved: the script says so."""
    real = first_events.oracle_rows

    def late(*a):
        r = real(*a)
        r[-1, 1] += 1
        return r

    monkeypatch.setattr(first_events, "oracle_rows", late)
    root = make_root(tmp_path)
    rc = first_events.main(["--workload", "tiny.poisson", "--seed",
                            "3000000011", "--trees", "2"], root=root,
                           check=lambda chips: jax.devices())
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and not out["equal"] and len(out["differ"]) == 2


# ------------------------------------------------------- planted faults
@pytest.mark.parametrize("fault", sorted(planted.FAULTS))
def test_planted_kernels_put_in_and_take_back_their_line(fault):
    from repro.core.engines import streaming
    from repro.kernels import stream_filter as sf

    mod = planted.planted_kernels(fault)
    assert mod is not sf
    assert mod.stream_filter_bytes_pallas_sparse is not \
        sf.stream_filter_bytes_pallas_sparse
    restore = planted.install(fault)
    try:
        assert streaming.sf.__name__ == "repro.kernels._planted_stream_filter"
    finally:
        restore()
    assert streaming.sf is sf


def test_a_fault_whose_line_is_gone_is_refused(monkeypatch):
    monkeypatch.setitem(planted.FAULTS, "gone", ("no such line\n", "x\n"))
    with pytest.raises(RuntimeError, match="walk has changed"):
        planted.planted_kernels("gone")
