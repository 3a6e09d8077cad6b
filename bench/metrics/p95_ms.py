"""p95_ms: the 95th percentile (nearest rank) of latency over every
request due in the window, from its due time in the open-loop schedule to
the delivery of its match list.  A request shed, failed or not delivered
by the drain's cut-off is counted at the cut-off, as late as the run can
see."""
from bench.harness import nearest_rank


def read(ctx):
    if ctx.latencies_ms is None or ctx.latencies_ms.size == 0:
        return None
    return nearest_rank(ctx.latencies_ms, 95)
