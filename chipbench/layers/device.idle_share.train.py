"""Share of the traced call's wall time in which no op ran on the device
(averaged over chips)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.chips or "traced_call" not in ctx["counters"]:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
