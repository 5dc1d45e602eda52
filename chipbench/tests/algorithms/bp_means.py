"""OCC BP-means, for the harness's tests only: the program's
`BPMeansTransaction` (greedy coordinate-pass propose, Gram-carry
validator; answer: (N, K_max) bool features) under a structural check.  It
shows that the jobs generator runs such a transaction from a file it finds
by name; it says nothing of whether BP-means is right, which a
configuration of its own proves against a reference of BPValidate."""
import jax.numpy as jnp

import flops

# The coordinate pass is XLA code, not a Pallas call.
PROPOSE_KERNEL = None


def transaction(cfg, seed):
    from repro.core import BPMeansTransaction
    return BPMeansTransaction(cfg["lam"], k_max=cfg["k_max"])


def check(x, answer, cfg, mix):
    """Broken promises of the answer's form: an answer that is no (N,
    K_max) bool; a point that holds a feature past the pool's count."""
    z, send, pool = answer
    if z.shape != (x.shape[0], cfg["k_max"]) or z.dtype != jnp.bool_:
        return {"answers_bad": x.shape[0]}
    live = jnp.arange(cfg["k_max"]) < pool.count
    return {"answers_bad": int(jnp.sum(jnp.any(z & ~live[None, :], -1)))}


def propose_work(k_start, accepted, pb, d):
    """The tests' count of the coordinate pass: for each point and live
    feature, a dot product and a residual update of D each."""
    f, b = flops.propose_epochs([k_start] + list(accepted), pb, d)
    return 2.0 * f[1:], b[1:]


def small(cfg):
    """Few slots, since the pass scans every one; at noise 0.2 and λ = 0.8
    a point of a component no feature holds makes one and the others do
    not: a feature for each component beside the mean."""
    return dict(cfg, n_components=16, n_points=4096, k_max=256, noise=0.2,
                lam=0.8, pb=min(cfg["pb"], 256))
