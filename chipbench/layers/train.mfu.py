"""Whole-step share of the chips' peak in the traced call: the propose
phase's algorithmic FLOPs (the cell's algorithm counts them; 2 pb K_e D an
epoch for DP-means) over the call's wall time, over chips times the bf16
peak."""


def read(ctx):
    call = ctx["counters"].get("traced_call")
    if call is None or call["seconds"] <= 0:
        return None
    c = ctx["counters"]
    f, _ = ctx["algorithm"].propose_work(call["k_start"], call["accepted"],
                                         c["pb"], c["dim"])
    peak = ctx["peaks"]["flops_per_s"] * c["chips"]
    return 100.0 * float(f.sum()) / call["seconds"] / peak
