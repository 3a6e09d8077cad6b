"""The trace reduction on hand-made events and on a small trace recorded
on one TPU v5e."""
from pathlib import Path

import pytest

from bench import harness
from bench import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
K = "stream_filter_bytes_pallas_sparse"
DENSE = "stream_filter_bytes_pallas"


def ev(name, start, dur, plane="/device:TPU:0", line="XLA Ops"):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7), (9, 9)]


def test_summarize_hand_made():
    events = [
        ev(f"%{K}.1 = (s32[3,8,128]) custom-call(...)", 0, 400),
        ev("%copy.3 = u32[4,8] copy(...)", 300, 200),      # overlaps
        ev(f"%{K}.1 = (s32[3,8,128]) custom-call(...)", 1000, 500),
        ev("%fusion.2 = f32[8] fusion(...)", 2000, 100),
        ev(f"%{K}_dense.1 = custom-call(...)", 3000, 50),  # another kernel
        ev("%copy.4 = u32[4,8] copy(...)", 100, 10, line="Async XLA Ops"),
        ev("TransferToDevice", 500, 450, plane="/host:CPU", line="t1"),
        ev("ScheduleWork", 1500, 450, plane="/host:CPU", line="t2"),
        ev("Execute", 1600, 50, plane="/host:CPU", line="t3"),
    ]
    s = tr.summarize(events, window_s=4000e-9, kernels=[K], n_chips=1)
    # busy: [0, 500] + [1000, 1500] + [2000, 2100] + [3000, 3050]
    assert s["busy_s"] == pytest.approx((500 + 500 + 100 + 50) * 1e-9)
    assert s["kernel_s"] == pytest.approx((400 + 500) * 1e-9)
    assert s["kernel_launches"] == 2
    assert s["kernel_s_per_chip"] == [s["kernel_s"]]
    assert s["kernel_launches_per_chip"] == [2]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops[K] == pytest.approx(900e-9)
    assert ops["copy"] == pytest.approx(200e-9)
    # gaps: [500, 1000] 500 ns, [1500, 2000] 500 ns, [2100, 3000] 900 ns
    gaps = s["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([900e-9, 500e-9, 500e-9])
    assert gaps[0][0] == "idle"
    assert {g[0] for g in gaps[1:]} == {"TransferToDevice", "ScheduleWork"}


def test_busy_averages_over_the_cells_chips():
    events = [ev("%a = x", 0, 100), ev("%a = x", 0, 300,
                                       plane="/device:TPU:1")]
    s = tr.summarize(events, window_s=1e-6, kernels=[K], n_chips=2)
    assert s["busy_s"] == pytest.approx(200e-9)


def test_recorded_v5e_trace():
    """Two batches of two documents (1,024 profiles in two kernel blocks
    over a generated 24-tag schema) on one TPU v5e: 98 XLA ops
    and 12 host events.  The expected values were counted by marking each
    op's nanoseconds on a bitmap of the 15,173,828 ns between the first
    op's start and the last op's end."""
    events = tr.load_events_json(str(DATA / "trace_v5e_small.json"))
    s = tr.summarize(events, window_s=15_173_828e-9, kernels=[K], n_chips=1)
    assert s["busy_s"] == pytest.approx(10_826_748e-9, abs=1e-12)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(
        1 - 10_826_748 / 15_173_828)
    # the two megakernel launches: 5,842,331 ns and 4,907,117 ns
    assert s["kernel_launches"] == 2
    assert s["kernel_s"] == pytest.approx((5_842_331 + 4_907_117) * 1e-9)
    assert s["breakdown"]["device_ops"][0][0] == K
    # the gap between the batches, 4,346,979 ns from 51,028,213 ns; the
    # host was reading the first batch's match buffer back
    name, length = s["breakdown"]["idle_gaps"][0]
    assert length == pytest.approx(4_346_979e-9)
    assert name == "np.asarray(jax.Array)"
    # the same numbers with both bytes kernels asked for: the dense one
    # never ran on this one-chip route
    assert tr.summarize(events, window_s=15_173_828e-9, kernels=[DENSE, K],
                        n_chips=1) == s
    assert s["kernel_s_per_chip"] == [s["kernel_s"]]
    assert s["kernel_launches_per_chip"] == [2]


def four_chip_events(per_chip_ns):
    """Each chip ``c`` runs the dense bytes kernel once per entry of
    ``per_chip_ns[c]``, 1 us apart, beside a copy and, on chip 0, an op
    of another kernel whose name only starts like it."""
    events = []
    for c, durs in enumerate(per_chip_ns):
        plane = f"/device:TPU:{c}"
        for i, d in enumerate(durs):
            events.append(ev(f"%{DENSE}.{i} = (s32[1,64]) custom-call(...)",
                             i * 1000, d, plane=plane))
            events.append(ev("%copy.1 = u8[64] copy(...)", i * 1000 + d, 5,
                             plane=plane))
    events.append(ev(f"%{DENSE}_dense_other.1 = custom-call(...)", 9000, 70))
    return events


def test_four_chips_give_kernel_time_per_chip():
    events = four_chip_events([[100, 100], [100, 120], [90, 90], []])
    s = tr.summarize(events, window_s=1e-5, kernels=[K, DENSE], n_chips=4)
    assert s["kernel_s_per_chip"] == pytest.approx(
        [200e-9, 220e-9, 180e-9, 0.0])
    assert s["kernel_launches_per_chip"] == [2, 2, 2, 0]
    assert s["kernel_s"] == pytest.approx(600e-9 / 4)
    assert s["kernel_launches"] == 1.5
    # busy per chip: kernels + copies, and the other op on chip 0
    assert s["busy_s"] == pytest.approx(
        (200 + 10 + 70 + 220 + 10 + 180 + 10) * 1e-9 / 4)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops[DENSE] == pytest.approx(600e-9 / 4)


@pytest.mark.parametrize("per_chip,want", [
    ([[100, 100], [100, 120], [90, 90], [105, 105]], 100 * (220 / 202.5 - 1)),
    ([[100], [100], [100], [100]], 0.0),
    ([[100], [100], [100], []], 100 * (100 / 75 - 1))])
def test_kernel_imbalance_pct(per_chip, want):
    s = tr.summarize(four_chip_events(per_chip), window_s=1e-5,
                     kernels=[DENSE], n_chips=4)
    read = harness.load_module(
        ROOT / "bench" / "metrics" / "kernel_imbalance_pct.py").read
    ctx = harness.Context("backlog", 1.0, 1.0, {}, {}, trace=s)
    assert read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("per_chip,n_chips", [([[100]], 1), ([[], []], 2)])
def test_kernel_imbalance_pct_is_silent_on_one_chip_or_no_kernel(
        per_chip, n_chips):
    s = tr.summarize(four_chip_events(per_chip), window_s=1e-5,
                     kernels=[DENSE], n_chips=n_chips)
    read = harness.load_module(
        ROOT / "bench" / "metrics" / "kernel_imbalance_pct.py").read
    assert read(harness.Context("backlog", 1.0, 1.0, {}, {},
                                trace=s)) is None
    assert read(harness.Context("backlog", 1.0, 1.0, {}, {})) is None
