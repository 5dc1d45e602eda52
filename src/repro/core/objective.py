"""Objectives from the paper.

J(C) = sum_x min_{mu in C} ||x - mu||^2 + lambda^2 |C|        (Eq. 5, DP-means / FL)
BP-means cost = sum_i ||x_i - Z_i F||^2 + lambda^2 K          (MAD-Bayes / BP-means)
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ref import MATMUL_PRECISION

__all__ = ["sq_dists", "dp_means_objective", "bp_means_objective"]


def sq_dists(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared euclidean distances (N, D) x (K, D) -> (N, K).

    Uses the expanded form ||x||^2 + ||mu||^2 - 2 x mu^T so the inner term is
    a single matmul (MXU-friendly; the Pallas kernel tiles the same algebra).
    Clamped at zero against fp cancellation.  The matmul runs at
    `MATMUL_PRECISION` (full f32) so that the validator, which measures
    payload pairs through here, sees the distances the propose kernel sees.
    """
    x = jnp.asarray(x)
    centers = jnp.asarray(centers)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # (N, 1)
    c2 = jnp.sum(centers * centers, axis=-1)[None, :]    # (1, K)
    cross = jnp.matmul(x, centers.T, precision=MATMUL_PRECISION)  # (N, K)
    return jnp.maximum(x2 + c2 - 2.0 * cross, 0.0)


def dp_means_objective(x: jnp.ndarray, centers: jnp.ndarray, lam: float,
                       mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Facility-location / DP-means objective J(C) (paper Eq. 5)."""
    d2 = sq_dists(x, centers)
    if mask is not None:
        d2 = jnp.where(mask[None, :], d2, jnp.inf)
        k = jnp.sum(mask)
    else:
        k = centers.shape[0]
    return jnp.sum(jnp.min(d2, axis=-1)) + lam * lam * k


def bp_means_objective(x: jnp.ndarray, z: jnp.ndarray, feats: jnp.ndarray,
                       lam: float, mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """BP-means cost: ||X - Z F||_F^2 + lambda^2 K."""
    if mask is not None:
        z = z * mask[None, :]
        k = jnp.sum(mask)
    else:
        k = feats.shape[0]
    resid = x - z.astype(x.dtype) @ feats
    return jnp.sum(resid * resid) + lam * lam * k
