"""Whole-step share of the chips' peak in the traced call: the propose
phase's algorithmic distance FLOPs (2 pb K_e D per epoch) over the call's
wall time, over chips times the bf16 peak."""
import flops


def read(ctx):
    call = ctx["counters"].get("traced_call")
    if call is None or call["seconds"] <= 0:
        return None
    c = ctx["counters"]
    f, _ = flops.propose_epochs([call["k_start"]] + list(call["accepted"]),
                                c["pb"], c["dim"])
    peak = ctx["peaks"]["flops_per_s"] * c["chips"]
    return 100.0 * float(f[1:].sum()) / call["seconds"] / peak
