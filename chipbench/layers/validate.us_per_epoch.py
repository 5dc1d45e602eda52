"""Device time per epoch of everything in the traced call but the propose
kernel: the validator's precompute and serial scan, writeback, and the
publish slices (busy time less kernel time, per chip, over the epochs)."""
import flops


def read(ctx):
    call = ctx["counters"].get("traced_call")
    red = ctx["trace"]
    if call is None or red is None or not red.chips:
        return None
    epochs = len(call["accepted"])
    n = len(red.chips)
    other = red.busy_s - red.kernel_seconds(flops.PROPOSE_KERNEL) / n
    return 1e6 * other / epochs if epochs else None
