"""Online Facility Location: serial (Meyerson [17]) and OCC-parallel (Alg. 4/5).

Serial OFL processes points in one pass: x becomes a facility with
probability min(1, d^2/lambda^2) where d is the distance to the nearest
open facility; otherwise it is assigned to that facility.

OCC OFL (Alg. 4): a point is *sent* to the validator with the probability
computed from the stale state C^{t-1}; the validator accepts it with the
conditional probability such that the *net* acceptance probability equals
the serial algorithm's with the up-to-date state (Appendix B.3, Eq. 2-4).

Bit-exact serializability: each point i owns one uniform draw
u_i = U(fold_in(key, i)).  Send iff u_i < min(1, d^2/lam^2); validator
accepts iff u_i < min(1, d*^2/lam^2).  Since d* <= d, the joint event is
exactly {u_i < min(1, d*^2/lam^2)} — the serial decision with the same u_i —
so distributed and serial runs agree draw-for-draw, which makes Thm 3.1
testable exactly rather than only in distribution.

The uniforms are counter-based in the *global* point index, so the
streaming surface (`OCCEngine.partial_fit`) reproduces a one-shot run over
the concatenated stream draw-for-draw as well — for ANY batch lengths: the
engine's partial-epoch carry keeps the stream's epoch partition identical
to the one-shot partition (tests/test_stream_carry.py).

The OCC version is a declarative `OFLTransaction` run by the unified
`OCCEngine` (core/engine.py); `occ_ofl` remains as the backward-compatible
wrapper returning `OFLResult`.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import OCCEngine, resolve_assignments
from repro.core.objective import dp_means_objective, sq_dists
from repro.core.occ import (
    CenterPool, OCCStats, ValidatePre, make_pool, nearest_center,
    nearest_center_with_new, serial_validate,
)

__all__ = ["OFLResult", "OFLTransaction", "point_uniforms", "serial_ofl",
           "occ_ofl"]


class OFLResult(NamedTuple):
    pool: CenterPool
    z: jnp.ndarray
    stats: OCCStats
    send: jnp.ndarray
    epoch_of: jnp.ndarray
    objective: jnp.ndarray


def point_uniforms(key: jax.Array, n: int, offset: int = 0) -> jnp.ndarray:
    """One counter-based uniform per global point index — shared by serial,
    OCC, and streaming runs."""
    idx = offset + jnp.arange(n)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    return jax.vmap(lambda k: jax.random.uniform(k))(keys)


@partial(jax.jit, static_argnames=("n",))
def _draw_uniforms(key: jax.Array, offset, *, n: int) -> jnp.ndarray:
    """`point_uniforms` as one compiled call, its ops under the named scope
    `occ.state`.  The offset is traced, so each span length compiles once
    however far into the stream it starts; the bits are `point_uniforms`'."""
    with jax.named_scope("occ.state"):
        return point_uniforms(key, n, offset)


def _ofl_accept(lam2):
    def accept_fn(pool: CenterPool, x_j, u_j):
        d2, ref = nearest_center(pool, x_j)
        p = jnp.minimum(1.0, d2 / lam2)   # empty pool -> inf/lam2 -> 1
        return u_j < p, x_j, ref
    return accept_fn


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class OFLTransaction:
    """OCC Online Facility Location as a transaction (Alg. 4/5): the
    per-point state is its counter-based uniform draw, making the validator
    decision the exact serial decision (App. B.3)."""
    lam: Any
    k_max: int
    key: jax.Array

    def tree_flatten(self):
        return (self.lam, self.key), (self.k_max,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        lam, key = children
        return cls(lam, aux[0], key)

    def _lam2(self, dtype):
        return jnp.asarray(self.lam, dtype) ** 2

    def init_pool(self, x):
        return make_pool(self.k_max, x.shape[-1], x.dtype)

    def make_state(self, x, offset: int = 0):
        return _draw_uniforms(self.key, offset, n=x.shape[0])

    def propose(self, pool, x_e, u_e):
        d2, idx = nearest_center(pool, x_e)
        # Threshold in d2's dtype — f32 on the Pallas backend regardless of
        # input dtype — so propose and both validator paths round λ² alike.
        p_send = jnp.minimum(1.0, d2 / self._lam2(d2.dtype))
        # Thread (u, d2, idx): the validator needs the point's uniform AND
        # can reuse the C^{t-1} distances instead of recomputing them.
        return u_e < p_send, x_e, (u_e, d2, idx), idx

    def precompute_accept(self, pool, payload_c, aux_c, count0):
        # Unified validator contract (DESIGN.md §11): one payload pairwise
        # matrix on the MXU; the per-step rule then needs only the point's
        # own uniform — a monotone threshold in d², so the log-depth
        # resolution applies (u < min(1, ·/λ²) commutes with min exactly).
        u, d2s, idxs = aux_c
        return ValidatePre(d2s, idxs, sq_dists(payload_c, payload_c), u)

    def accept_pre(self, d2_cur, u_j):
        p = jnp.minimum(1.0, d2_cur / self._lam2(d2_cur.dtype))
        return u_j < p

    def accept(self, pool, x_j, aux_j, count0):
        # REFERENCE ONLY (core/_reference.py): accept iff u < min(1, d*²/λ²)
        # with d* over the current pool — only the new slots are measured
        # fresh (App. B.3).
        u_j, d2s_j, idxs_j = aux_j
        d2, ref = nearest_center_with_new(pool, x_j, d2s_j, idxs_j, count0)
        p = jnp.minimum(1.0, d2 / self._lam2(d2.dtype))
        return u_j < p, x_j, ref

    def writeback(self, send, slots, outs, safe, valid):
        return resolve_assignments(send, slots, outs, safe, valid)

    def refine(self, pool, x, z):
        return pool   # single-pass algorithm: no refinement phase

    def objective(self, x, z, pool):
        return dp_means_objective(x, pool.centers, self.lam, pool.mask)


@partial(jax.jit, static_argnames=("k_max",))
def serial_ofl(x: jnp.ndarray, u: jnp.ndarray, lam: float, k_max: int):
    """Serial OFL over points in the given order, with per-point uniforms u."""
    pool = make_pool(k_max, x.shape[-1], x.dtype)
    lam2 = jnp.asarray(lam, x.dtype) ** 2
    send = jnp.ones((x.shape[0],), bool)
    pool, slots, refs = serial_validate(pool, send, x, _ofl_accept(lam2), aux=u)
    z = jnp.where(slots >= 0, slots, refs).astype(jnp.int32)
    return pool, z


def occ_ofl(
    x: jnp.ndarray,
    lam: float,
    pb: int,
    key: jax.Array,
    k_max: int = 256,
    validate_cap: int | None | str = None,
    mesh: jax.sharding.Mesh | None = None,
    data_axis: str = "data",
) -> OFLResult:
    """OCC Online Facility Location (Alg. 4) — convenience wrapper running
    `OFLTransaction` under `OCCEngine`.  Single pass by construction."""
    txn = OFLTransaction(lam, k_max, key)
    eng = OCCEngine(txn, pb, validate_cap=validate_cap, mesh=mesh,
                    data_axis=data_axis)
    res = eng.run(x)
    obj = txn.objective(x, res.assign, res.pool)
    return OFLResult(res.pool, res.assign, res.stats, res.send,
                     res.epoch_of, obj)
