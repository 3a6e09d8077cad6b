"""stage_ms_per_batch: the stage's host-clock seconds per batch
(``FilterStage.stats`` ``seconds`` / ``batches``, staging, device call
and match-list read-back) over the window's batches."""


def read(ctx):
    a, b = ctx.edge0["stage"], ctx.edge1["stage"]
    n = b["batches"] - a["batches"]
    if n <= 0:
        return None
    return 1e3 * (b["seconds"] - a["seconds"]) / n
