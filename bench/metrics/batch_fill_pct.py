"""batch_fill_pct: mean fill of the batches the serve loop
resolved during the window, from the change of the loop's own
``batch_fill`` (a running mean) over the window's batches."""


def read(ctx):
    a, b = ctx.edge0["loop"], ctx.edge1["loop"]
    n0, n1 = a["delivered_batches"], b["delivered_batches"]
    if n1 <= n0:
        return None
    return 100.0 * (b["batch_fill"] * n1 - a["batch_fill"] * n0) / (n1 - n0)
