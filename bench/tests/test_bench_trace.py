"""The trace reduction on hand-made events and on a small trace recorded
on one TPU v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
K = "stream_filter_bytes_pallas_sparse"


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
    s = tr.summarize(events, window_s=4000e-9, kernel=K, n_chips=1)
    # busy: [0, 500] + [1000, 1500] + [2000, 2100] + [3000, 3050]
    assert s["busy_s"] == pytest.approx((500 + 500 + 100 + 50) * 1e-9)
    assert s["kernel_s"] == pytest.approx((400 + 500) * 1e-9)
    assert s["kernel_launches"] == 2
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
    s = tr.summarize(events, window_s=1e-6, kernel=K, n_chips=2)
    assert s["busy_s"] == pytest.approx(200e-9)


def test_recorded_v5e_trace():
    """Two batches of two documents (1,024 profiles in two kernel blocks
    over a generated 24-tag schema) on one TPU v5e: 98 XLA ops
    and 12 host events.  The expected values were counted by marking each
    op's nanoseconds on a bitmap of the 15,173,828 ns between the first
    op's start and the last op's end."""
    events = tr.load_events_json(str(DATA / "trace_v5e_small.json"))
    s = tr.summarize(events, window_s=15_173_828e-9, kernel=K, n_chips=1)
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
