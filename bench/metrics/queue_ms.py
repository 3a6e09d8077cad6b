"""queue_ms: mean milliseconds from admission to dispatch per request the
serve loop resolved in the window: the change of the loop's ``queue_s``
(the sum of each resolved request's ``t_dispatch - t_submit``) over the
change of ``completed``.  ``None`` where the loop keeps no ``queue_s``."""


def read(ctx):
    a, b = ctx.edge0.get("loop", {}), ctx.edge1.get("loop", {})
    if "queue_s" not in a or "queue_s" not in b:
        return None
    n = b["completed"] - a["completed"]
    if n <= 0:
        return None
    return 1e3 * (b["queue_s"] - a["queue_s"]) / n
