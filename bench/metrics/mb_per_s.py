"""mb_per_s: wire bytes (10^6) of the documents whose match lists were
delivered inside the window, over the window's seconds.  The window runs
from one delivery to the first delivery at or past ``--seconds`` later,
so it holds whole batches."""


def read(ctx):
    if ctx.traffic != "backlog" or ctx.window_s <= 0:
        return None
    return ctx.window_bytes / 1e6 / ctx.window_s
