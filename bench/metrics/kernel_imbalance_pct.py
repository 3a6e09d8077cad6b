"""kernel_imbalance_pct: how far the busiest chip's kernel time in the
traced window lies above the mean over the cell's chips:
100 * (largest per-chip kernel seconds / their mean - 1).  A chip that
ran no kernel counts at 0 s.  ``None`` on one chip or where no kernel
ran."""


def read(ctx):
    t = ctx.trace
    per = t.get("kernel_s_per_chip", []) if t is not None else []
    if len(per) < 2 or sum(per) <= 0:
        return None
    return 100.0 * (max(per) * len(per) / sum(per) - 1.0)
