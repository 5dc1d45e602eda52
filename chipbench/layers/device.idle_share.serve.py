"""Share of the serving window in which no op ran on the device."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.chips or "bucket_fill" not in ctx["counters"]:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
