"""Share of the device's busy time in the traced call spent in the propose
kernel's ops (summed over chips, over the chips' summed busy time); nothing
where the cell's algorithm has no propose kernel."""


def read(ctx):
    red = ctx["trace"]
    kernel = ctx["algorithm"].PROPOSE_KERNEL
    if red is None or not red.chips or "traced_call" not in ctx["counters"] \
            or red.busy_s <= 0 or kernel is None:
        return None
    k = red.kernel_seconds(kernel)
    if k <= 0:
        return None
    return 100.0 * k / (red.busy_s * len(red.chips))
