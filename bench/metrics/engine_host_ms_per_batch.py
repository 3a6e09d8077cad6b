"""engine_host_ms_per_batch: the worker's host milliseconds per batch
without its wait for the chip: the change of the stage's span counters
``pack_s`` (``ByteBatch.from_buffers``), ``launch_s`` (staging, H2D and
the kernel's enqueue) and ``expand_s`` (class hits to subscribers) over
the change of ``batches``.  ``None`` where the stage keeps no spans."""

KEYS = ("pack_s", "launch_s", "expand_s")


def read(ctx):
    a, b = ctx.edge0.get("stage", {}), ctx.edge1.get("stage", {})
    if any(k not in a or k not in b for k in KEYS):
        return None
    n = b["batches"] - a["batches"]
    if n <= 0:
        return None
    return 1e3 * sum(b[k] - a[k] for k in KEYS) / n
