"""The readers of the program's span and counter metrics, on hand-made
window edges: the value, and ``None`` on the edges of a program that
keeps no such counters or on a window without a batch."""
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    return harness.load_module(harness.reader_path(name, ROOT))


def ctx(loop0, loop1, stage0, stage1):
    return harness.Context("poisson", 1.0, 51.0,
                           edge0={"loop": loop0, "stage": stage0},
                           edge1={"loop": loop1, "stage": stage1})


# the parent's edges: the loop's and the stage's counters without spans
OLD_LOOP = {"completed": 10, "batches": 3, "batch_fill": 0.5,
            "delivered_batches": 3}
OLD_STAGE = {"batches": 3, "docs": 10, "bytes": 1000, "seconds": 0.3}


def span_edges(n0, n1):
    """Edges ``n0`` and ``n1`` batches into a run in which every batch
    holds 4 requests, each queued 5 ms, packs in 1 ms, launches in 2 ms,
    waits 7 ms for the chip, expands in 0.5 ms and fans out in 0.25 ms."""
    def loop(n):
        return dict(OLD_LOOP, completed=4 * n, batches=n,
                    delivered_batches=n, queue_s=4 * n * 0.005,
                    fan_out_s=n * 0.00025, deliver_s=n * 0.001,
                    wait_fill_s=n * 0.02)

    def stage(n):
        return dict(OLD_STAGE, batches=n, seconds=n * 0.0105,
                    pack_s=n * 0.001, launch_s=n * 0.002,
                    device_s=n * 0.007, expand_s=n * 0.0005)

    return ctx(loop(n0), loop(n1), stage(n0), stage(n1))


@pytest.mark.parametrize("name,want", [
    ("queue_ms.poisson", 5.0),
    ("engine_host_ms_per_batch.backlog", 3.5),
    ("engine_host_ms_per_batch.poisson", 3.5),
    ("fan_out_ms_per_batch.backlog", 0.25),
    ("fan_out_ms_per_batch.poisson", 0.25)])
def test_reader_value(name, want):
    assert reader(name).read(span_edges(7, 107)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["queue_ms.poisson",
                                  "engine_host_ms_per_batch.poisson",
                                  "fan_out_ms_per_batch.poisson"])
def test_reader_is_silent_on_the_parents_edges(name):
    old = ctx(OLD_LOOP, dict(OLD_LOOP, completed=50, delivered_batches=9),
              OLD_STAGE, dict(OLD_STAGE, batches=9, seconds=0.9))
    assert reader(name).read(old) is None


@pytest.mark.parametrize("name", ["queue_ms.poisson",
                                  "engine_host_ms_per_batch.poisson",
                                  "fan_out_ms_per_batch.poisson"])
def test_reader_is_silent_on_a_window_without_a_batch(name):
    assert reader(name).read(span_edges(7, 7)) is None


def test_readers_are_found_by_quantity():
    for name in ("queue_ms.poisson", "engine_host_ms_per_batch.backlog",
                 "fan_out_ms_per_batch.poisson"):
        path = harness.reader_path(name, ROOT)
        assert path.name == name.rsplit(".", 1)[0] + ".py"
        assert path.exists()


def test_every_metric_in_the_benchmark_has_a_reader():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(reader(m["name"]), "read"), m["name"]
