"""kernel_us_per_chunk: microseconds of the bytes megakernel per chunk
that its walk fetched from HBM: 10⁶ · the kernel's seconds in the trace
over the chunks of the traced launches.  The stage counts the chunks of
every (segment, state-word block) program in ``stream_chunks``; its
change over the change of ``batches`` is the chunks per launch, and the
trace gives the launches.  A copy that the walk does not hide shows here
as time per chunk.  ``None`` without a trace, or where the stage counts
no chunks."""


def read(ctx):
    t = ctx.trace
    a, b = ctx.edge0.get("stage", {}), ctx.edge1.get("stage", {})
    if t is None or "stream_chunks" not in a or "stream_chunks" not in b:
        return None
    chunks = b["stream_chunks"] - a["stream_chunks"]
    batches = b["batches"] - a["batches"]
    if chunks <= 0 or batches <= 0 or t["kernel_s"] <= 0 \
            or not t["kernel_launches"]:
        return None
    return 1e6 * t["kernel_s"] / (t["kernel_launches"] * chunks / batches)
