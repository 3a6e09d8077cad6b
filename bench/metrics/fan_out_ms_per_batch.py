"""fan_out_ms_per_batch: the completer's milliseconds per delivered batch
in the fan-out of match lists to subscribers and the stage's accounting:
the change of the loop's ``fan_out_s`` span counter over the change of
the batches handed to ``deliver``.  ``None`` where the loop keeps no
``fan_out_s``."""


def read(ctx):
    a, b = ctx.edge0.get("loop", {}), ctx.edge1.get("loop", {})
    if "fan_out_s" not in a or "fan_out_s" not in b:
        return None
    n = b["delivered_batches"] - a["delivered_batches"]
    if n <= 0:
        return None
    return 1e3 * (b["fan_out_s"] - a["fan_out_s"]) / n
