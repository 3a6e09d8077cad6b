"""Measured megakernel autotune: timed search + persisted config cache.

:meth:`repro.core.engines.base.FilterEngine.autotune_blocks` picks the
megakernel launch shape from a *static* VMEM/SMEM budget formula — a safe
default, but blind to everything the formula cannot see (DMA latency vs
compute overlap, grid iteration order, packing density).  This module
closes the loop the way every serious kernel library does:

* :func:`search` — run the actual one-launch bytes→verdict engine over a
  representative workload for every candidate ``(blk, byte_chunk,
  grid_order, segment_target)`` combination, best-of-``trials`` wall
  clock each, and return the fastest.
* a tiny **JSON cache** keyed by plan shape
  (:func:`plan_key`: backend × padded states × tags × depth × word
  multiple) and persisted at :func:`cache_path` (the
  ``REPRO_AUTOTUNE_CACHE`` env var, default
  ``~/.cache/repro/autotune.json``) — engines constructed with
  ``autotune="measured"`` overlay the cached best config at ``plan()``
  time (:meth:`repro.core.engines.streaming.StreamingEngine.kernel_config`),
  so the search cost is paid once per plan shape per machine.

CLI (exercised by CI with a 2-trial cap under interpret)::

    python -m repro.kernels.autotune --queries 64 --trials 2

Writes/updates the cache and prints the per-candidate timings as JSON.

Migration note: :data:`KEY_VERSION` 3 dropped the ``ep_tile``
dimension (the fused epilogue no longer has an emission window); older
keys are simply never read again — re-run the search to repopulate.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import tempfile
import time
from typing import Any, Mapping, Sequence

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE = "~/.cache/repro/autotune.json"

#: bumped whenever the tunable-config schema changes (v3: no
#: ``ep_tile``); part of every :func:`plan_key`, so old-schema entries
#: miss cleanly
KEY_VERSION = 3

#: candidate grids for the measured search (kept small: the search is
#: measured, so every candidate costs a compile + ``trials`` timed runs)
DEFAULT_BLKS = (32, 64, 128)
DEFAULT_BYTE_CHUNKS = (128, 256, 512)
DEFAULT_GRID_ORDERS = ("bg", "gb")
DEFAULT_SEGMENT_TARGETS = (2048, 4096)


# ------------------------------------------------------------------- cache
def cache_path(path: str | None = None) -> str:
    """Resolve the cache file: explicit arg → env var → default."""
    return os.path.expanduser(
        path or os.environ.get(CACHE_ENV) or DEFAULT_CACHE)


def plan_key(backend: str, n_states: int, n_tags: int, max_depth: int,
             state_multiple: int) -> str:
    """Cache key: everything the launch shape may legitimately depend
    on, nothing it must not (batch contents, query text) — prefixed by
    :data:`KEY_VERSION` so schema changes invalidate old entries."""
    return (f"v{KEY_VERSION}:{backend}:s{int(n_states)}:t{int(n_tags)}"
            f":d{int(max_depth)}:w{int(state_multiple)}")


def load_cache(path: str | None = None) -> dict[str, Any]:
    """Read the cache file ({} on missing/corrupt — never raises)."""
    p = cache_path(path)
    try:
        with open(p) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def save_cache(entries: Mapping[str, Any],
               path: str | None = None) -> str:
    """Atomically persist the cache (tmp file + rename)."""
    p = cache_path(path)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p) or ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump({"version": 1, "entries": dict(entries)}, fh,
                      indent=2, sort_keys=True)
        os.replace(tmp, p)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return p


def cached_config(key: str, path: str | None = None) -> dict | None:
    """Best known config for ``key`` (None on miss) — what
    ``autotune="measured"`` engines overlay at plan time."""
    entry = load_cache(path).get(key)
    if isinstance(entry, dict) and "config" in entry:
        return dict(entry["config"])
    return None


# ------------------------------------------------------------------ search
def _time_engine(eng, bb, trials: int) -> float:
    """Best-of-``trials`` wall seconds for one packed filter_bytes call
    plus one packed sparse call (the fused-epilogue path); the first,
    untimed calls pay compilation."""
    eng.filter_bytes(bb, pack=True)
    eng.filter_bytes_sparse(bb, pack=True)
    best = float("inf")
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        eng.filter_bytes(bb, pack=True)
        eng.filter_bytes_sparse(bb, pack=True)
        best = min(best, time.perf_counter() - t0)
    return best


def search(nfa, dictionary, bb, *, max_depth: int | None = None,
           blks: Sequence[int] = DEFAULT_BLKS,
           byte_chunks: Sequence[int] = DEFAULT_BYTE_CHUNKS,
           grid_orders: Sequence[str] = DEFAULT_GRID_ORDERS,
           segment_targets: Sequence[int] = DEFAULT_SEGMENT_TARGETS,
           trials: int = 3, interpret: bool | None = None,
           cache: bool = True, cache_file: str | None = None
           ) -> tuple[dict, list[dict]]:
    """Measured search over the megakernel launch shape.

    Times the REAL one-launch bytes path (``filter_bytes(pack=True)``)
    on ``bb`` for every feasible candidate, returns ``(best, rows)``
    where ``rows`` carries every candidate's config + seconds (or its
    skip reason), and — with ``cache=True`` — persists the winner under
    this plan shape's :func:`plan_key`.
    """
    from ..core import engines
    from ..core.engines.base import _round_up
    from ..kernels import interpret_default
    from ..kernels.parse import DEFAULT_MAX_DEPTH

    if max_depth is None:
        max_depth = DEFAULT_MAX_DEPTH
    rows: list[dict] = []
    best: dict | None = None
    for blk, bc, go, st in itertools.product(
            blks, byte_chunks, grid_orders, segment_targets):
        cfg = {"blk": int(blk), "byte_chunk": int(bc),
               "grid_order": str(go), "segment_target": int(st)}
        try:
            eng = engines.create(
                "streaming", nfa, dictionary=dictionary,
                kernel="pallas", kernel_interpret=interpret,
                max_depth=max_depth, pack=True, **cfg)
            secs = _time_engine(eng, bb, trials)
        except Exception as e:  # infeasible layout (blk too small, …)
            rows.append({**cfg, "skipped": f"{type(e).__name__}: {e}"})
            continue
        row = {**cfg, "seconds": secs}
        rows.append(row)
        if best is None or secs < best["seconds"]:
            best = row
    if best is None:
        raise RuntimeError("autotune: no feasible candidate "
                           f"(tried {len(rows)}; see rows for reasons)")
    if cache:
        backend = ("interpret"
                   if (interpret if interpret is not None
                       else interpret_default())
                   else "compiled")
        key = plan_key(backend, _round_up(nfa.n_states, 32), nfa.n_tags,
                       max_depth, 32)
        entries = load_cache(cache_file)
        entries[key] = {
            "config": {k: best[k] for k in
                       ("blk", "byte_chunk", "grid_order",
                        "segment_target")},
            "seconds": best["seconds"],
            "trials": int(trials),
            "timestamp": time.time(),
        }
        save_cache(entries, cache_file)
    return best, rows


# --------------------------------------------------------------------- CLI
def _int_list(s: str) -> tuple[int, ...]:
    return tuple(int(x) for x in s.split(",") if x.strip())


def main(argv: Sequence[str] | None = None) -> int:
    from ..core.dictionary import TagDictionary
    from ..core.events import ByteBatch
    from ..core.nfa import compile_queries
    from ..data.generator import DTD, gen_corpus, gen_profiles

    ap = argparse.ArgumentParser(
        description="measured megakernel autotune search")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--n-tags", type=int, default=24)
    ap.add_argument("--docs", type=int, default=16)
    ap.add_argument("--nodes", type=int, default=60)
    ap.add_argument("--text-fill", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--blks", type=_int_list, default=DEFAULT_BLKS)
    ap.add_argument("--byte-chunks", type=_int_list,
                    default=DEFAULT_BYTE_CHUNKS)
    ap.add_argument("--grid-orders",
                    type=lambda s: tuple(x for x in s.split(",") if x),
                    default=DEFAULT_GRID_ORDERS)
    ap.add_argument("--segment-targets", type=_int_list,
                    default=DEFAULT_SEGMENT_TARGETS)
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default ${CACHE_ENV} or "
                         f"{DEFAULT_CACHE})")
    args = ap.parse_args(argv)

    dtd = DTD.generate(n_tags=args.n_tags, seed=args.seed)
    d = TagDictionary()
    dtd.register(d)
    qs = gen_profiles(dtd, n=args.queries, length=4, p_wild=0.1,
                      p_desc=0.3, seed=args.seed)
    nfa = compile_queries(qs, d, shared=True)
    # skewed lengths on purpose: packing quality is part of what the
    # segment_target dimension is tuned against
    docs = (gen_corpus(dtd, n_docs=max(1, args.docs // 4),
                       nodes_per_doc=args.nodes, seed=args.seed)
            + gen_corpus(dtd, n_docs=args.docs - max(1, args.docs // 4),
                         nodes_per_doc=max(2, args.nodes // 8),
                         seed=args.seed + 1))
    bb = ByteBatch.from_streams(docs, text_fill=args.text_fill, bucket=256)
    best, rows = search(
        nfa, d, bb, blks=args.blks, byte_chunks=args.byte_chunks,
        grid_orders=args.grid_orders, segment_targets=args.segment_targets,
        trials=args.trials, cache_file=args.cache)
    print(json.dumps({"best": best, "rows": rows,
                      "cache": cache_path(args.cache)}, indent=2))
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
