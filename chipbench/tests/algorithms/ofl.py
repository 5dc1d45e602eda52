"""OCC online facility location, for the harness's tests only: the
program's `OFLTransaction` (per-point state: one uniform from a key drawn
from the seed; answer: (N,) int32 facilities) under a structural check.
It shows that the jobs generator runs such a transaction from a file it
finds by name; it says nothing of whether OFL is right, which a
configuration of its own proves against a reference of OFL's rule."""
import jax.numpy as jnp

import common
import flops
import reference

PROPOSE_KERNEL = "dpmeans_assign"


def transaction(cfg, seed):
    """One key from the seed: every job of a run draws the same
    uniforms."""
    from repro.core import OFLTransaction
    return OFLTransaction(cfg["lam"], cfg["k_max"], common.seed_key(seed))


def check(x, answer, cfg, mix):
    """Broken promises of the answer's form: an assignment that is no
    int32 per point, or out of [0, K); a facility that is not the point
    that opened it, or one opened by a point that was not sent
    (`reference.job_structure`)."""
    assign, send, pool = answer
    if assign.shape != (x.shape[0],) or assign.dtype != jnp.int32:
        return {"answers_bad": x.shape[0]}
    bad = reference.job_structure(x, pool.centers, pool.count, assign, send,
                                  pb=cfg["pb"])[0]
    return {"answers_bad": int(bad)}


def propose_work(k_start, accepted, pb, d):
    """The nearest-facility propose: DP-means' count."""
    f, b = flops.propose_epochs([k_start] + list(accepted), pb, d)
    return f[1:], b[1:]


def small(cfg):
    """λ = 3: a point opens a facility with probability d²/9, and under
    half of the slots fill."""
    return dict(cfg, n_components=256, n_points=8192, k_max=2048,
                noise=0.8, lam=3.0, pb=min(cfg["pb"], 256))
