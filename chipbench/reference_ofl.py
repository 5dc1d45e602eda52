"""The plain reference of OCC online facility location (Meyerson's OFL as the
paper's Alg. 4/5 runs it), in straightforward `jax.numpy` at float32 and
HIGHEST matmul precision.  It imports nothing of the program.

Each point i owns one uniform u_i = U(fold_in(key, i)), i its index in the
job.  A point opens a facility (becomes one) iff u_i < min(1, d²/λ²), d
its distance to the nearest open facility; otherwise it is assigned to
that facility.  OCC decides the points in epochs of `pb`, in the order of
Thm 3.1: a point is *sent* iff u_i < min(1, d²/λ²) against the facilities
open at its epoch's start; a point not sent is assigned to the nearest of
those; sent points are decided in index order against every facility
opened before them.  Since u_i < min(1, d²/λ²) is d² > λ²·u_i, OFL's rule
is DP-means' with a threshold of its own per point, t_i = λ²·u_i: the
chip-size check (`algorithms/ofl.py`) reads the answer with
`reference.py`'s DP-means readings given those thresholds.

`serial_ofl` is the point-by-point oracle of the CPU tests.
`control_answers` is the bfloat16 control of the chip-size check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference


@functools.partial(jax.jit, static_argnames=("n",))
def uniforms(key, n: int, offset=0):
    """u_i = U(fold_in(key, i)) for i = offset .. offset + n - 1: one
    counter-based draw per point, by its index in the job."""
    idx = offset + jnp.arange(n, dtype=jnp.int32)
    return jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(key, i)))(idx)


def thresholds(u, lam):
    """t_i = λ²·u_i in float32: a point lies beyond its threshold iff it
    opens (or is sent)."""
    return jnp.float32(lam) ** 2 * jnp.asarray(u, jnp.float32)


@jax.jit
def _nearest(x, facilities, count):
    """(d², index) of the nearest of the first `count` facilities to each
    row of x; (inf, -1) where there is none."""
    d2 = reference.sq_dists(x, facilities)
    d2 = jnp.where(jnp.arange(facilities.shape[0])[None, :] < count, d2,
                   jnp.inf)
    idx = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    best = jnp.min(d2, axis=-1)
    return best, jnp.where(jnp.isfinite(best), idx, -1)


def _opens(u, d2, lam2) -> np.ndarray:
    """u < min(1, d²/λ²), in float32 as the rule is written."""
    p = jnp.minimum(jnp.float32(1.0), jnp.asarray(d2) / lam2)
    return np.asarray(jnp.asarray(u) < p)


def serial_ofl(x, u, lam: float, pb: int):
    """OFL over the points of x in epochs of pb, in the order of Thm 3.1.

    Returns (assign (N,) int32, send (N,) bool, facilities (F, D)): each
    point's facility (its own slot where it opened one), whether it was
    sent, and the facilities in the order they opened."""
    x = jnp.asarray(x, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    n, d = x.shape
    lam2 = jnp.float32(lam) ** 2
    buf = jnp.zeros((n, d), jnp.float32)      # every point could open one
    count = 0
    assign = np.full(n, -1, np.int32)
    send = np.zeros(n, bool)
    for lo in range(0, n, pb):
        hi = min(lo + pb, n)
        d2, idx = _nearest(x[lo:hi], buf, count)
        sent = _opens(u[lo:hi], d2, lam2)
        assign[lo:hi] = np.where(sent, -1, np.asarray(idx))
        send[lo:hi] = sent
        for i in range(lo, hi):
            if not sent[i - lo]:
                continue
            d2_i, idx_i = _nearest(x[i:i + 1], buf, count)
            if _opens(u[i], d2_i[0], lam2):
                buf = buf.at[count].set(x[i])
                assign[i] = count
                count += 1
            else:
                assign[i] = int(idx_i[0])
    return assign, send, buf[:count]


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def control_answers(x, centers, avail, k_start, t, *, block: int = 1024,
                    dtype=jnp.bfloat16):
    """The control: each point's assignment and send decision recomputed in
    `dtype` against the facilities it was decided against, with its own
    threshold t_i (`reference.control_answers` with a threshold per point
    in place of one λ²)."""
    n = x.shape[0]
    cols = jnp.arange(centers.shape[0], dtype=jnp.int32)[None, :]

    def one(args):
        xb, avb, ksb, tb = args
        d2 = reference.sq_dists(xb, centers, dtype)
        a = jnp.argmin(jnp.where(cols < avb[:, None], d2, jnp.inf), -1)
        m_st = jnp.min(jnp.where(cols < ksb[:, None], d2, jnp.inf), -1)
        return a.astype(jnp.int32), m_st > tb

    nb = n // block
    r = lambda a: a.reshape((nb, block) + a.shape[1:])
    a, s = jax.lax.map(one, (r(x), r(avail), r(k_start), r(t)))
    return a.reshape(n), s.reshape(n)
