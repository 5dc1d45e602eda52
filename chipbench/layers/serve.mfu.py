"""Whole serving path's share of the chips' peak over the traced window: the
algorithmic distance FLOPs of every answered row (2 K D per row against the
K live centers) over the window, over chips times the bf16 peak."""
import flops


def read(ctx):
    c = ctx["counters"]
    red = ctx["trace"]
    if red is None or not red.chips or "group_rows" not in c \
            or red.window_s <= 0:
        return None
    f, _ = flops.serve_dispatch(c["group_rows"], c["n_centers"], c["dim"])
    peak = ctx["peaks"]["flops_per_s"] * c["chips"]
    return 100.0 * float(f.sum()) / red.window_s / peak
