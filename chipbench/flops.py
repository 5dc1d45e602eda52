"""Algorithmic work of the kernels, from shapes and live counts.

These count what the algorithm needs, not what an implementation does: no
tile, no padding row, no dead slot of the pool.  So a kernel's share of its
roofline reads the same work whichever code runs it, and a change that
skips work it never needed cannot push the share past 100%.
"""
from __future__ import annotations

import numpy as np

F32 = 4


def propose_epochs(accepted, pb: int, d: int):
    """(flops, bytes) per epoch of the propose phase: each of the epoch's
    pb points against the K_e centers live at the epoch's start, K_e the
    sum of the earlier epochs' accepts.  Each center and each point is read
    once."""
    acc = np.asarray(accepted, np.float64)
    k_e = np.concatenate([[0.0], np.cumsum(acc)[:-1]])
    flops = 2.0 * pb * k_e * d
    nbytes = F32 * d * (k_e + pb)
    return flops, nbytes


def serve_dispatch(rows, k: int, d: int):
    """(flops, bytes) of one flat probe over k live centers for `rows` real
    query rows (padding rows are not counted)."""
    rows = np.asarray(rows, np.float64)
    return 2.0 * rows * k * d, F32 * d * (k + rows)


def min_seconds(flops, nbytes, peaks: dict) -> float:
    """The least time the chip could take, call by call: the larger of the
    operations over the peak rate and the bytes over the HBM bandwidth."""
    t = np.maximum(np.asarray(flops) / peaks["flops_per_s"],
                   np.asarray(nbytes) / peaks["hbm_bytes_per_s"])
    return float(np.sum(t))


# Kernel names as the device trace shows them: a Pallas call's op is named
# after the jitted function that makes it.
PROPOSE_KERNEL = "dpmeans_assign"
SERVE_KERNELS = ("dpmeans_assign", "topk_stream")
