"""One benchmark run: build a cell from its files, warm up, drive the
window through the served path, check what it delivered, print the
result.

The system under test is ``ServeLoop.submit`` over
``FilterStage(engine="streaming", sparse=True, keep_unmatched=True)``;
the loop hands every batch's match lists to :meth:`Run.deliver`, which
stamps them on the run's monotonic clock.  Everything else — the
traffic, the clock, the reference, the metrics — is the benchmark's own.

Files, found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the deployment (schema, profile set,
  document sizes, stage settings) and the module of its plain reference;
* ``bench/traffic/<mix>.json`` — arrival kind, rate, loop settings;
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(ctx)``
  returns the value or ``None`` when the run has nothing to read; a
  metric split by mix (``<quantity>.<mix>``) without a file of its own
  is read by ``bench/metrics/<quantity>.py``;
* ``bench/peaks.json`` — device peaks by ``device_kind``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from .traffic import arrivals, generator

#: the checkout this file belongs to
ROOT = Path(__file__).resolve().parents[1]
#: a request not delivered this long after the window closed counts as
#: never delivered
DRAIN_S = 60.0
#: JAX's compile events; every one of them inside the window is a shape
#: the warm-up missed (a persistent-cache hit still traces and lowers)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the kernel that each stage route (``stats["verdict_paths"]``) runs on
#: the chip, where it is not the configuration's ``kernel``: the 2-D
#: data-parallel route runs the dense bytes kernel on every chip and
#: sparsifies its verdict on the host
ROUTE_KERNELS = {"dense-2d": "stream_filter_bytes_pallas"}


class NoChip(RuntimeError):
    """The run would not measure a TPU."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class CellSpec:
    """A cell and the files it names."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "CellSpec":
        spec = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have: {', '.join(cells)})")
        cell = cells[name]
        cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
        mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
        chips, batch = int(cell["chips"]), int(mix["loop"]["max_batch"])
        if batch % chips:
            # every chip of a data-parallel cell takes an equal share
            raise SystemExit(f"workload {name!r}: a batch of {batch} does "
                             f"not divide over {chips} chips")

        def mine(metrics):
            return [m for m in metrics
                    if name in m.get("workloads", [name])]

        return cls(name, chips, load_json(root / cfg["file"]),
                   mix, mine(spec["end_to_end"]), mine(spec["per_layer"]))


def check_platform(chips: int) -> list:
    """The TPU devices to measure, or :class:`NoChip`."""
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        raise NoChip("REPRO_PALLAS_INTERPRET asks for the Pallas interpreter")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devices[0].platform!r}, not on a TPU")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devices)} present")
    return devices


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every
    program kept, so only a checkout's first run compiles."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


@dataclass
class Delivery:
    t: float
    docs: list            # RoutedDocument per document of the batch


@dataclass
class Context:
    """What a metric reader can read: counters at the window's edges,
    the window's deliveries and latencies, and the trace reduction."""

    traffic: str
    setup_s: float
    window_s: float
    edge0: dict
    edge1: dict
    window_bytes: int = 0
    window_docs: int = 0
    rows_per_doc: float = 0.0
    latencies_ms: np.ndarray | None = None
    trace: dict | None = None
    peaks: dict = field(default_factory=dict)


class Run:
    """One run of one cell at one seed."""

    def __init__(self, spec: CellSpec, seed: int, seconds: float,
                 t_process: float):
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.t_process = t_process
        self.clock = time.monotonic
        self.deliveries: list[Delivery] = []
        self.n_batches = 0        # deliver calls: the loop's resolved batches
        self.compiles: list[float] = []
        self.window_started = threading.Event()
        self.window_closed = threading.Event()
        self.t_window = (math.inf, math.inf)

    # --------------------------------------------------------------- build
    def build(self):
        cfg, mix = self.spec.config, self.spec.mix
        from repro.core.dictionary import TagDictionary
        from repro.data.filter_stage import FilterStage

        t0 = time.perf_counter()
        self.dep = generator.deployment(cfg)
        self.pool = generator.Pool.build(self.dep, cfg["documents"],
                                         self.seed)
        row = cfg["stage"]["byte_bucket"]
        if self.pool.max_bytes > row:
            raise ValueError(f"a document of {self.pool.max_bytes} bytes "
                             f"outgrew the {row}-byte row")
        t1 = time.perf_counter()
        d = TagDictionary()
        for n in self.dep.names:
            d.add(n)
        self.max_batch = int(mix["loop"]["max_batch"])
        self.n_distinct = len(set(self.dep.profiles))
        st = cfg["stage"]
        # every (document, distinct profile) pair of a batch fits the
        # match buffer: it never overflows into the dense re-run.  A cell
        # on several chips lays its batch over them on the mesh's "data"
        # axis, each chip holding the whole plan
        chips = self.spec.chips
        self.stage = FilterStage(
            self.dep.profiles, d, engine=st["engine"], sparse=st["sparse"],
            keep_unmatched=st["keep_unmatched"], batch_size=self.max_batch,
            byte_bucket=row, data_shards=chips,
            engine_options={"match_cap": self.max_batch * self.n_distinct})
        laid = dict(self.stage.mesh.shape) if self.stage.mesh else {}
        if chips > 1 and laid != {"data": chips, "model": 1}:
            raise NoChip(f"{chips} data-parallel chips asked for, the "
                         f"stage's mesh is {laid}")
        meta = getattr(self.stage._eng, "plan_", None)
        meta = meta.meta if meta is not None else {}
        log(f"{len(self.dep.profiles)} profiles ({self.n_distinct} "
            f"distinct), states {meta.get('n_states')}, kernel blocks "
            f"{meta.get('n_blocks')} of {meta.get('blk')}; pool "
            f"{len(self.pool)} trees, "
            f"{sum(len(t) for t in self.pool.templates)} bytes "
            f"({t1 - t0:.3f} s), stage {time.perf_counter() - t1:.3f} s")
        self.stream = generator.Stream(self.pool, self.seed)

    def warm(self) -> None:
        """Every batch shape the mix's loop can issue, each run once."""
        sizes = ([self.max_batch] if self.spec.mix["arrivals"]["kind"]
                 == "backlog" else range(1, self.max_batch + 1))
        rng = generator.rng_for(self.seed, "warm")
        t0 = time.perf_counter()
        for b in sizes:
            bufs = [self.pool.salted(i % len(self.pool), rng)
                    for i in range(b)]
            list(self.stage.route_bytes(bufs))
        log(f"warmed {len(sizes)} batch shapes in "
            f"{time.perf_counter() - t0:.3f} s")

    # -------------------------------------------------------------- window
    def deliver(self, routed) -> None:
        t = self.clock()
        self.deliveries.append(Delivery(t, routed))
        self.n_batches += 1
        if self.spec.mix["arrivals"]["kind"] != "backlog":
            return
        # the edges are read here, on the completer thread that alone
        # updates the loop's and the stage's counters
        if len(self.deliveries) == self.spec.mix["warm_batches"]:
            self.t_window = (t, math.inf)
            self.edge0 = self._edge()
            self.window_started.set()
        elif (self.window_started.is_set()
              and not self.window_closed.is_set()
              and t >= self.t_window[0] + self.seconds):
            self.t_window = (self.t_window[0], t)
            self.edge1 = self._edge()
            self.window_closed.set()

    def _on_compile(self, event: str, *_a, **_k) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(self.clock())

    def loop_kwargs(self) -> dict:
        lp = self.spec.mix["loop"]
        return dict(max_batch=self.max_batch,
                    deadline_ms=lp["deadline_ms"], queue_cap=lp["queue_cap"],
                    max_inflight=lp["max_inflight"], overload=lp["overload"],
                    deliver=self.deliver)

    def drive(self, tracer) -> None:
        from repro.serve.loop import ServeLoop

        self.loop = ServeLoop(self.stage, **self.loop_kwargs())
        try:
            if self.spec.mix["arrivals"]["kind"] == "backlog":
                self._backlog(tracer)
            else:
                self._open_loop(tracer)
        finally:
            self.loop.close()
        if self.due is not None:
            self.edge1 = self._edge()
        self.summary = self.loop.slo_summary()
        self.routes = dict(self.stage.stats["verdict_paths"])

    def _edge(self) -> dict:
        """The loop's and the stage's counters at one edge of the window."""
        stats = dict(self.stage.stats)
        stats.pop("verdict_paths")
        return {"loop": dict(self.loop.slo_summary(),
                             delivered_batches=self.n_batches),
                "stage": stats}

    def _backlog(self, tracer) -> None:
        """Back-to-back submissions into a blocking loop; the window runs
        from the ``warm_batches``-th delivery to the first delivery at or
        past ``seconds`` later."""
        self.tickets = []
        self.tree_of = self.stream.trees
        gc.collect()
        gc.freeze()
        while not self.window_closed.is_set():
            self.tickets.append(self.loop.submit(self.stream.next()))
            if self.window_started.is_set():
                tracer.start()
        tracer.stop()
        self.t_setup = self.t_window[0] - self.t_process
        # whole batches only: the flush at close keeps the warmed shape
        while len(self.tickets) % self.max_batch:
            self.tickets.append(self.loop.submit(self.stream.next()))
        self.due = None

    def _submit_on(self, due: np.ndarray):
        """Submit one payload at each due offset from now; returns the
        tickets, how late each submission was (s) and the schedule's
        start; ``tree_of`` names each ticket's tree."""
        tickets, late = [], np.empty(due.size)
        first = len(self.stream.trees)
        t0 = self.clock() + 0.05
        for i, d in enumerate(due):
            payload = self.stream.next()
            lag = t0 + d - self.clock()
            if lag > 0:
                time.sleep(lag)
            late[i] = self.clock() - (t0 + d)
            tickets.append(self.loop.submit(payload))
        self.tree_of = self.stream.trees[first:]
        return tickets, late, t0

    def _open_loop(self, tracer) -> None:
        """Open-loop schedule: request ``i`` is due at ``t0 + due[i]``
        whatever the loop does; payloads are salted before they are
        due, so the generator's own work is off the schedule."""
        warm_s = self.spec.mix.get("warm_s", 0)
        if warm_s:
            # the same traffic for warm_s seconds first, through the same
            # loop, delivered in full before the window opens
            warm = self._submit_on(arrivals.schedule(
                self.spec.mix, warm_s, generator.rng_for(self.seed, "warm")))
            for tk in warm[0]:
                tk.done.wait()
            # a batch is handed to deliver right after its tickets are
            # done; a few seconds cover that last step
            n = sum(1 for tk in warm[0] if not tk.shed)
            cutoff = self.clock() + 5.0
            while (sum(len(d.docs) for d in self.deliveries) < n
                   and self.clock() < cutoff):
                time.sleep(0.001)
            self.deliveries.clear()
        due = arrivals.schedule(self.spec.mix, self.seconds,
                                generator.rng_for(self.seed, "arrivals"))
        gc.collect()
        gc.freeze()
        self.edge0 = self._edge()
        tracer.start()
        self.tickets, late, t0 = self._submit_on(due)
        self.t_setup = t0 - self.t_process
        self.t_window = (t0, t0 + self.seconds)
        while self.clock() < self.t_window[1]:
            time.sleep(self.t_window[1] - self.clock())
        tracer.stop()
        cutoff = self.t_window[1] + DRAIN_S
        for tk in self.tickets:
            tk.done.wait(timeout=max(0.0, cutoff - self.clock()))
        self.due = t0 + due
        self.cutoff = cutoff
        self.late_ms = late * 1e3

    # ------------------------------------------------------------- results
    def collect(self) -> tuple[Context, dict]:
        """The window's numbers, and per-request delivery records."""
        t_a, t_b = self.t_window
        seq_of = [tk.seq for tk in self.tickets]
        delivered: dict[int, list] = {}
        for dl in self.deliveries:
            for rd in dl.docs:
                delivered.setdefault(rd.doc_index, []).append((dl.t, rd))
        ctx = Context(self.spec.mix["arrivals"]["kind"], self.t_setup,
                      t_b - t_a, edge0=self.edge0, edge1=self.edge1)
        if self.due is None:
            # backlog: delivery is in admission order, so the window's
            # requests are the admission numbers past the last one
            # delivered when it opened, up to the last one delivered when
            # it closed; one of them left undelivered is missing
            def last_seq(t):
                return max((rd.doc_index for dl in self.deliveries
                            if dl.t <= t for rd in dl.docs), default=-1)

            lo, hi = last_seq(t_a), last_seq(t_b)
            in_window = [i for i, s in enumerate(seq_of) if lo < s <= hi]
        else:
            # open loop: the requests due inside the window
            in_window = list(range(len(self.tickets)))
            lat = np.empty(len(in_window))
            for i in in_window:
                s = seq_of[i]
                t = delivered[s][0][0] if s in delivered else self.cutoff
                lat[i] = t - self.due[i]
            ctx.latencies_ms = lat * 1e3
        ctx.window_docs = len(in_window)
        ctx.window_bytes = sum(
            len(self.tickets[i].payload) for i in in_window
            if seq_of[i] in delivered
            and t_a < delivered[seq_of[i]][0][0] <= t_b)
        rows = sum(len({self.dep.profiles[int(g)]
                        for _, rd in delivered.get(seq_of[i], [])
                        for g in rd.matched_profiles})
                   for i in in_window)
        ctx.rows_per_doc = rows / max(1, len(in_window))
        return ctx, {"in_window": in_window, "delivered": delivered,
                     "seq_of": seq_of}

    def verify(self, rec: dict, reference) -> dict:
        """Every request of the window against the configuration's plain
        reference (the module ``reference``):
        ``wrong`` delivered lists that differ (or a document delivered
        twice), ``missing`` requests admitted and never delivered by the
        cut-off."""
        wrong = missing = 0
        matcher = reference.Reference(self.dep.profiles, self.dep.names)
        # the reference runs once per tree, on the first payload of it
        # that the window sent; every other payload of the tree is
        # checked to decode to the same events
        memo: dict[int, np.ndarray] = {}
        for i in rec["in_window"]:
            tk, s = self.tickets[i], rec["seq_of"][i]
            if tk.shed:
                continue
            got = rec["delivered"].get(s, [])
            if not got or tk.error is not None:
                missing += 1
                continue
            tree = self.tree_of[i]
            if tree not in memo:
                kinds, tags = reference.decode(tk.payload)
                if not (np.array_equal(kinds, self.pool.kinds[tree])
                        and np.array_equal(tags, self.pool.tags[tree])):
                    raise RuntimeError("a salted payload changed structure")
                memo[tree] = matcher.match(kinds, tags)
            if len(got) != 1 or not np.array_equal(
                    np.sort(np.asarray(got[0][1].matched_profiles)),
                    memo[tree]):
                wrong += 1
        return {"wrong_lists": (wrong, 0), "missing": (missing, 0)}


class Tracer:
    """The profiler around the window of a ``--trace 1`` run."""

    def __init__(self, on: bool, clock):
        self.on, self.clock = on, clock
        self.dir = None
        self.span = (0.0, 0.0)

    def start(self) -> None:
        if self.on and self.dir is None:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            # no Python tracer: it would time every Python call of the
            # loop's threads and slow the very host path being measured
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = (self.clock(), 0.0)

    def stop(self) -> None:
        if self.on and self.dir is not None and not self.span[1]:
            import jax

            self.span = (self.span[0], self.clock())
            jax.profiler.stop_trace()

    def reduce(self, kernels: list[str], n_chips: int) -> dict | None:
        if self.dir is None:
            return None
        from . import trace_reduce

        try:
            events = trace_reduce.load_xplane(self.dir)
            return trace_reduce.summarize(
                events, window_s=self.span[1] - self.span[0],
                kernels=kernels, n_chips=n_chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def route_kernels(config: dict, routes: dict) -> list[str]:
    """The kernels that the routes a run took launched on the chip."""
    return sorted({ROUTE_KERNELS.get(r, config["kernel"]) for r in routes})


def nearest_rank(xs: np.ndarray, q: float) -> float:
    """The ``q``-th percentile by nearest rank: an observed value, so a
    tail of never-delivered requests shows as itself."""
    xs = np.sort(np.asarray(xs, float))
    k = max(1, math.ceil(q / 100.0 * xs.size))
    return float(xs[k - 1])


def reader_path(name: str, root: Path) -> Path:
    """``bench/metrics/<name>.py``, or for ``<quantity>.<mix>`` without a
    file of its own, the quantity's reader."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        return reader_path(name.rsplit(".", 1)[0], root)
    return path


def read_metrics(metrics: list[dict], ctx: Context, root: Path) -> dict:
    out = {}
    for m in metrics:
        v = load_module(reader_path(m["name"], root)).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def device_info(devices, ctx: Context | None) -> dict:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, AttributeError):
            pass
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if ctx is not None and ctx.trace is not None:
        out["busy_s"] = ctx.trace["busy_s"]
        out["window_s"] = ctx.trace["window_s"]
    return out


def peaks_for(kind: str, root: Path) -> dict:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_process: float | None = None,
         root: Path = ROOT, check=None) -> int:
    """Run one cell; the last line of standard output is the result."""
    t_process = time.monotonic() if t_process is None else t_process
    args = parse_args(argv)
    spec = CellSpec.load(args.workload, root)
    try:
        devices = (check or check_platform)(spec.chips)
    except NoChip as e:
        log(f"no measurement: {e}")
        return 2
    log(f"device {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache(root)}")
    import jax.monitoring

    run = Run(spec, args.seed, args.seconds, t_process)
    peaks = peaks_for(devices[0].device_kind, root) if args.trace else {}
    jax.monitoring.register_event_duration_secs_listener(run._on_compile)
    try:
        run.build()
        run.warm()
        tracer = Tracer(bool(args.trace), run.clock)
        run.drive(tracer)
    finally:
        jax.monitoring.unregister_event_duration_listener(run._on_compile)
    ctx, rec = run.collect()
    in_window = sum(1 for t in run.compiles
                    if run.t_window[0] <= t <= run.t_window[1])
    log(f"compiles inside the window: {in_window}")
    s = run.summary
    log(f"loop: admitted {s['admitted']} shed {s['shed']} failed "
        f"{s['failed']} quarantined {s['quarantined']} batches "
        f"{s['batches']} (size {s['size_closes']}, deadline "
        f"{s['deadline_closes']}, flush {s['flush_closes']}); routes "
        f"{run.routes}")
    if run.due is not None:
        lm = run.late_ms
        log(f"requests due in the window: {lm.size}; generator late p50 "
            f"{np.median(lm):.3f} ms p99 {nearest_rank(lm, 99):.3f} ms "
            f"max {lm.max():.3f} ms")
    if args.trace:
        ctx.peaks = peaks
        ctx.trace = tracer.reduce(route_kernels(spec.config, run.routes),
                                  spec.chips)
        if ctx.trace is not None:
            log(f"kernel per chip: launches "
                f"{ctx.trace['kernel_launches_per_chip']}, seconds "
                f"{ctx.trace['kernel_s_per_chip']}")
    metrics = read_metrics(spec.per_layer if args.trace else spec.end_to_end,
                           ctx, root)
    dev = device_info(devices, ctx)
    # the reference runs once the device's peak was read and the
    # program's state is gone
    del run.loop, run.stage
    gc.collect()
    t0 = time.perf_counter()
    checks = run.verify(rec, load_module(
        root / "bench" / "configs" / f"{spec.config['reference']}.py"))
    log(f"reference over {len(rec['in_window'])} requests: "
        f"{time.perf_counter() - t0:.3f} s")
    failed = sum(1 for i in rec["in_window"]
                 if run.tickets[i].shed or run.tickets[i].error is not None)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": ctx.window_docs, "failed": failed,
              "metrics": metrics, "device": dev}
    if ctx.trace is not None:
        result["breakdown"] = ctx.trace["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
    return 0
