"""Share of the device's busy time in collective ops (all-gather,
all-reduce, ...), averaged over the chips; only where the cell has more
than one chip."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.chips or len(red.chips) < 2 or red.busy_s <= 0:
        return None
    return 100.0 * red.collective_s / red.busy_s
