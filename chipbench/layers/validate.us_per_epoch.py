"""Device time per epoch of everything in the traced call but the propose
kernel: the validator's precompute and serial scan, writeback, and the
publish slices (busy time less kernel time, per chip, over the epochs);
nothing where the cell's algorithm has no propose kernel to take away."""


def read(ctx):
    call = ctx["counters"].get("traced_call")
    red = ctx["trace"]
    kernel = ctx["algorithm"].PROPOSE_KERNEL
    if call is None or red is None or not red.chips or kernel is None:
        return None
    epochs = len(call["accepted"])
    n = len(red.chips)
    other = red.busy_s - red.kernel_seconds(kernel) / n
    return 1e6 * other / epochs if epochs else None
