"""DP-means: serial (Alg. 1) and OCC-parallel (Alg. 3 + DPValidate Alg. 2).

The OCC version is a ~40-line declarative `DPMeansTransaction` run by the
unified `OCCEngine` (core/engine.py): one compiled `lax.scan` over epochs
replaces the legacy hand-rolled Python epoch loop.  `occ_dp_means` remains
as the backward-compatible convenience wrapper returning `DPMeansResult`.

Serial equivalence (Thm 3.1): within an epoch, non-proposed points (whose
assignment depends only on C^{t-1}) are ordered before proposed points,
which are validated in global index order.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (
    OCCEngine, accumulate_pass_stats, resolve_assignments,
)
from repro.core.objective import dp_means_objective, sq_dists
from repro.core.occ import (
    CenterPool, OCCStats, ValidatePre, make_pool, nearest_center,
    nearest_center_with_new, serial_validate,
)

__all__ = ["DPMeansResult", "DPMeansTransaction", "serial_dp_means_pass",
           "serial_dp_means", "occ_dp_means", "thm31_permutation"]


class DPMeansResult(NamedTuple):
    pool: CenterPool
    z: jnp.ndarray              # (N,) int32 — assignment to pool slot
    stats: OCCStats             # per-epoch proposed / accepted counts
    send: jnp.ndarray           # (N,) bool — point was sent to the validator
    epoch_of: jnp.ndarray       # (N,) int32 — epoch each point was processed in
    n_iters: int
    objective: jnp.ndarray


def _dp_accept(lam2):
    """DPValidate accept rule: accept iff not within lambda of any center."""
    def accept_fn(pool: CenterPool, x_j, aux_j):
        d2, ref = nearest_center(pool, x_j)
        return d2 > lam2, x_j, ref
    return accept_fn


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class DPMeansTransaction:
    """DP-means as an OCC transaction (Alg. 3 optimistic phase + Alg. 2
    DPValidate): propose a point as a new cluster iff it is farther than
    lambda from every center of C^{t-1}."""
    lam: Any
    k_max: int = 256

    def tree_flatten(self):
        return (self.lam,), (self.k_max,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    def _lam2(self, dtype):
        return jnp.asarray(self.lam, dtype) ** 2

    def init_pool(self, x):
        return make_pool(self.k_max, x.shape[-1], x.dtype)

    def make_state(self, x, offset: int = 0):
        return ()

    def propose(self, pool, x_e, state_e):
        d2, idx = nearest_center(pool, x_e)
        # Thread (d2, idx) to the validator: accept/precompute_accept reuse
        # them instead of recomputing the C^{t-1} distances from scratch.
        # Threshold in d2's dtype — f32 on the Pallas backend regardless of
        # input dtype — so propose and both validator paths round λ² alike.
        return d2 > self._lam2(d2.dtype), x_e, (d2, idx), idx

    def precompute_accept(self, pool, payload_c, aux_c, count0):
        # Unified validator contract (DESIGN.md §11): the C^{t-1} distances
        # were already found by propose (threaded in aux); the only fresh
        # MXU work is the payload pairwise matrix — after which DPValidate
        # is pure scalar (and, being a monotone threshold rule, eligible
        # for the log-depth resolution).
        d2s, idxs = aux_c
        return ValidatePre(d2s, idxs, sq_dists(payload_c, payload_c), None)

    def accept_pre(self, d2_cur, aux_j):
        return d2_cur > self._lam2(d2_cur.dtype)

    def accept(self, pool, x_j, aux_j, count0):
        # REFERENCE ONLY (core/_reference.py): per-step recompute in which
        # only this epoch's new slots (>= count0) are measured fresh; the
        # C^{t-1} part comes threaded from propose.
        d2s_j, idxs_j = aux_j
        d2, ref = nearest_center_with_new(pool, x_j, d2s_j, idxs_j, count0)
        return d2 > self._lam2(d2.dtype), x_j, ref

    def writeback(self, send, slots, outs, safe, valid):
        return resolve_assignments(send, slots, outs, safe, valid)

    def refine(self, pool, x, z):
        return _recompute_means(x, z, pool)

    def objective(self, x, z, pool):
        return dp_means_objective(x, pool.centers, self.lam, pool.mask)


# ---------------------------------------------------------------------------
# Serial DP-means (Alg. 1)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k_max",))
def serial_dp_means_pass(x: jnp.ndarray, lam: float, k_max: int,
                         pool: CenterPool | None = None):
    """One serial pass of Alg. 1's inner loop: scan points in order,
    assigning to the nearest center or creating a new one.

    Equivalent to validating *every* point serially — the degenerate OCC run
    with P = b = 1.  Returns (pool, z).
    """
    if pool is None:
        pool = make_pool(k_max, x.shape[-1], x.dtype)
    lam2 = jnp.asarray(lam, x.dtype) ** 2
    send = jnp.ones((x.shape[0],), bool)
    pool, slots, refs = serial_validate(pool, send, x, _dp_accept(lam2))
    z = jnp.where(slots >= 0, slots, refs).astype(jnp.int32)
    return pool, z


def _recompute_means(x: jnp.ndarray, z: jnp.ndarray, pool: CenterPool) -> CenterPool:
    """Second phase of Alg. 1/3: mu_k <- Mean({x_i | z_i = k}).

    Slots with no assigned points keep their previous vector (cannot happen
    within the creating iteration; can after reassignment in later ones).
    Trivially parallel: segment sums are psum-able over the data axis.
    """
    k_max = pool.centers.shape[0]
    zc = jnp.clip(z, 0, k_max - 1)
    valid = z >= 0
    w = valid.astype(x.dtype)
    sums = jax.ops.segment_sum(x * w[:, None], zc, num_segments=k_max)
    cnts = jax.ops.segment_sum(w, zc, num_segments=k_max)
    means = sums / jnp.maximum(cnts, 1.0)[:, None]
    new_centers = jnp.where((cnts > 0)[:, None] & pool.mask[:, None], means, pool.centers)
    return pool._replace(centers=new_centers)


def serial_dp_means(x: jnp.ndarray, lam: float, k_max: int = 256,
                    max_iters: int = 20) -> DPMeansResult:
    """Full serial DP-means (Alg. 1): alternate the assignment/creation pass
    with the centroid recomputation until assignments are fixed."""
    n = x.shape[0]
    pool = make_pool(k_max, x.shape[-1], x.dtype)
    z_prev = None
    it = 0
    for it in range(1, max_iters + 1):
        pool, z = serial_dp_means_pass(x, lam, k_max, pool)
        pool = _recompute_means(x, z, pool)
        if z_prev is not None and bool(jnp.all(z == z_prev)):
            break
        z_prev = z
    obj = dp_means_objective(x, pool.centers, lam, pool.mask)
    t = np.zeros((1,), np.int32)
    return DPMeansResult(pool, z, OCCStats(t, t), jnp.zeros((n,), bool),
                         jnp.zeros((n,), jnp.int32), it, obj)


# ---------------------------------------------------------------------------
# OCC DP-means (Alg. 3) — compatibility wrapper over the engine
# ---------------------------------------------------------------------------

def occ_dp_means(
    x: jnp.ndarray,
    lam: float,
    pb: int,
    k_max: int = 256,
    max_iters: int = 1,
    bootstrap: bool = False,
    validate_cap: int | None | str = None,
    mesh: jax.sharding.Mesh | None = None,
    data_axis: str = "data",
) -> DPMeansResult:
    """OCC DP-means (Alg. 3) — convenience wrapper running
    `DPMeansTransaction` under `OCCEngine`.

    Args:
      x: (N, D) data.  pb: points per epoch (the paper's P*b product — only
      the product matters algorithmically; the mesh supplies the physical P).
      max_iters: outer while-loop passes (1 = the paper's Fig-3 setting).
      bootstrap: serially pre-process the first pb/16 points (paper §4.2).
      validate_cap: bounded-master compaction — an int, None, or "adaptive"
      for the Thm-3.3-sized window (see OCCEngine; bit-identical results).
      mesh: optional device mesh; epoch inputs are sharded over `data_axis`
      and the optimistic phase parallelizes under GSPMD while the validation
      scan executes replicated (SPMD re-execution of the master).
    """
    n = x.shape[0]
    txn = DPMeansTransaction(lam, k_max)
    eng = OCCEngine(txn, pb, validate_cap=validate_cap, mesh=mesh,
                    data_axis=data_axis)
    nb = min(n, max(1, pb // 16)) if bootstrap else 0

    z = jnp.full((n,), -1, jnp.int32)
    send = jnp.zeros((n,), bool)
    epoch_of = jnp.zeros((n,), jnp.int32)
    stat_parts: list[OCCStats] = []
    epoch_base = 0
    z_prev = None
    it_done = 0
    pool = None
    for it in range(1, max_iters + 1):
        it_done = it
        if it == 1:
            res = eng.run(x, n_bootstrap=nb)
            z, send, epoch_of = res.assign, res.send, res.epoch_of
        else:
            # Bootstrapped points keep their serial-prefix assignment; later
            # passes re-run only the bulk-synchronous epochs (seed semantics).
            res = eng.run(x[nb:], pool=pool)
            z = z.at[nb:].set(res.assign)
            send = send.at[nb:].set(res.send)
            epoch_of = epoch_of.at[nb:].set(res.epoch_of + epoch_base)
        # Every pass's validator load is recorded — epochs number globally
        # across passes, so stats[t] lines up with epoch_of == t.
        stat_parts.append(res.stats)
        epoch_base += res.stats.proposed.shape[0]
        pool = txn.refine(res.pool, x, z)
        if z_prev is not None and bool(jnp.all(z == z_prev)):
            break
        z_prev = z
    stats = accumulate_pass_stats(stat_parts)
    obj = txn.objective(x, z, pool)
    return DPMeansResult(pool, z, stats, send, epoch_of, it_done, obj)


def thm31_permutation(result: DPMeansResult, n: int) -> np.ndarray:
    """Build the serial order of Thm 3.1 from an OCC run: epochs in order;
    within an epoch, non-validated points (index order) precede validated
    points (validation = index order)."""
    send = np.asarray(result.send)
    epoch = np.asarray(result.epoch_of)
    idx = np.arange(n)
    order = np.lexsort((idx, send.astype(np.int32), epoch))
    return idx[order]
