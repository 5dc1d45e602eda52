"""Share of their roofline the serving kernels reach over the window: the
least time the chip could take for each dispatch's algorithmic work (its
real rows against every live center, each center read once) over the
summed device time of the assign and top-k kernels' ops."""
import flops


def read(ctx):
    c = ctx["counters"]
    red = ctx["trace"]
    if red is None or not red.chips or "group_rows" not in c:
        return None
    t = sum(red.kernel_seconds(k) for k in flops.SERVE_KERNELS)
    if t <= 0:
        return None
    f, b = flops.serve_dispatch(c["group_rows"], c["n_centers"], c["dim"])
    return 100.0 * flops.min_seconds(f, b, ctx["peaks"]) / t
