"""Profiler trace -> device busy time, kernel time, breakdown.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, flattened to
:class:`Event` rows.  On a TPU, plane ``/device:TPU:<n>`` holds one line
per kind of device activity; its ``XLA Ops`` line holds every operation
the chip ran, the megakernel among them as a ``custom-call`` whose event
name starts with the kernel's name (``%stream_filter_bytes_pallas_sparse``
today).  Host threads are lines of ``/host:CPU``.

* busy: the union of the ``XLA Ops`` intervals of each chip, averaged
  over the chips of the cell;
* kernel time: the summed durations of the ops whose name starts with
  ``%<kernel>`` (or ``<kernel>``) for any of the run's kernels, and how
  many ran, averaged over the chips and per chip;
* breakdown: the ten ops with the most device time per chip, and the
  ten longest gaps between busy intervals of chip 0, each named by what
  the host was doing in it (see :func:`idle_gaps`).
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import asdict, dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(trace_dir: str) -> list[Event]:
    """Every event of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files in {trace_dir}")
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def load_events_json(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(**row) for row in json.load(f)]


def dump_events_json(events: list[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([asdict(e) for e in events], f, indent=0)


def short_name(name: str) -> str:
    """``%stream_filter_bytes_pallas_sparse.1 = (...) custom-call(...)``
    -> ``stream_filter_bytes_pallas_sparse``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_ops(events: list[Event]) -> dict[int, list[Event]]:
    """Chip index -> its ``XLA Ops`` events."""
    chips: dict[int, list[Event]] = {}
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and e.line == OPS_LINE and e.dur_ns > 0:
            chips.setdefault(int(m.group(1)), []).append(e)
    return chips


def idle_gaps(busy: list[tuple[float, float]], host: list[Event],
              k: int = 10) -> list[list]:
    """The ``k`` longest gaps between busy intervals, each named by the
    host event that covers most of it among those that began inside it
    (work the host took up once the chip had gone idle), else among all
    (a wait that began before); ``idle`` when no host event overlaps."""
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:]) if b[0] > a[1]),
                  reverse=True)[:k]
    out = []
    for length, s, e in gaps:
        best = {True: ("idle", 0.0), False: ("idle", 0.0)}
        for h in host:
            c = min(e, h.end_ns) - max(s, h.start_ns)
            inside = h.start_ns >= s
            if c > best[inside][1]:
                best[inside] = (short_name(h.name), c)
        name = best[True][0] if best[True][1] > 0 else best[False][0]
        out.append([name, length / 1e9])
    return out


def summarize(events: list[Event], *, window_s: float, kernels: list[str],
              n_chips: int) -> dict:
    """Busy seconds, kernel seconds and launches (mean over the cell's
    ``n_chips`` chips, and per chip ``0 .. n_chips - 1``), and the
    breakdown."""
    chips = device_ops(events)
    busy_per_chip = {c: union([(e.start_ns, e.end_ns) for e in evs])
                     for c, evs in chips.items()}
    busy_s = sum(sum(e - s for s, e in iv)
                 for iv in busy_per_chip.values()) / 1e9 / n_chips
    alts = "|".join(re.escape(k) for k in kernels)
    pat = re.compile(rf"^%?(?:{alts})(\.\d+)?( |$)")
    kern = {c: [e for e in chips.get(c, []) if pat.match(e.name)]
            for c in range(n_chips)}
    kernel_s = [sum(e.dur_ns for e in kern[c]) / 1e9 for c in range(n_chips)]
    launches = [len(kern[c]) for c in range(n_chips)]
    by_name: dict[str, float] = {}
    for evs in chips.values():
        for e in evs:
            n = short_name(e.name)
            by_name[n] = by_name.get(n, 0.0) + e.dur_ns / 1e9 / n_chips
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    host = [e for e in events if e.plane == HOST_PLANE and e.dur_ns > 0]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "kernel_s": sum(kernel_s) / n_chips,
        "kernel_launches": sum(launches) / n_chips,
        "kernel_s_per_chip": kernel_s,
        "kernel_launches_per_chip": launches,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": idle_gaps(busy_per_chip.get(0, []), host),
        },
    }
