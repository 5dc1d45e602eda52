"""Share of its roofline the propose kernel reaches in the traced call: the
least time the chip could take for the epochs' algorithmic work (each of
pb points against the K_e centers live at the epoch's start) over the
summed device time of the kernel's ops."""
import flops


def read(ctx):
    call = ctx["counters"].get("traced_call")
    red = ctx["trace"]
    if call is None or red is None or not red.chips:
        return None
    t = red.kernel_seconds(flops.PROPOSE_KERNEL)
    if t <= 0:
        return None
    acc = list(call["accepted"])
    f, b = flops.propose_epochs([call["k_start"]] + acc, ctx["counters"]["pb"],
                                ctx["counters"]["dim"])
    return 100.0 * flops.min_seconds(f[1:], b[1:], ctx["peaks"]) / t
