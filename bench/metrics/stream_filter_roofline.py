"""stream_filter_roofline: the megakernel's share of its HBM
roofline.  The least HBM traffic a batch needs is its wire bytes read
once and one 12-byte (document, profile, first event) row written per
distinct profile it matches; over the chip's HBM bandwidth that is the
least time, divided by the kernel's device time in the trace.  HBM is the
bound because the kernel does no floating-point work that a published
peak would cover."""


def read(ctx):
    t = ctx.trace
    a, b = ctx.edge0["stage"], ctx.edge1["stage"]
    batches = b["batches"] - a["batches"]
    if t is None or t["kernel_s"] <= 0 or not t["kernel_launches"] \
            or batches <= 0:
        return None
    docs = (b["docs"] - a["docs"]) / batches
    per_launch = ((b["bytes"] - a["bytes"]) / batches
                  + 12.0 * ctx.rows_per_doc * docs)
    least_s = per_launch * t["kernel_launches"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["kernel_s"]
