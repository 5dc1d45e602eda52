"""Share of its roofline the propose kernel reaches in the traced call: the
least time the chip could take for the epochs' algorithmic work (the cell's
algorithm counts it; for DP-means each of pb points against the K_e centers
live at the epoch's start) over the summed device time of the kernel's ops;
nothing where the algorithm has no propose kernel."""
import flops


def read(ctx):
    call = ctx["counters"].get("traced_call")
    red = ctx["trace"]
    algo = ctx["algorithm"]
    if call is None or red is None or not red.chips \
            or algo.PROPOSE_KERNEL is None:
        return None
    t = red.kernel_seconds(algo.PROPOSE_KERNEL)
    if t <= 0:
        return None
    f, b = algo.propose_work(call["k_start"], call["accepted"],
                             ctx["counters"]["pb"], ctx["counters"]["dim"])
    return 100.0 * flops.min_seconds(f, b, ctx["peaks"]) / t
