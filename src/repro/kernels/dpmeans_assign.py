"""Pallas TPU kernel for the OCC hot loop: pairwise sq-distance + argmin.

TPU adaptation of the paper's `argmin_{mu in C} ||x - mu||` (DESIGN.md §6/§9):
instead of a GPU-style point-per-thread gather, the distance matrix block is
an MXU matmul (||x||^2 + ||mu||^2 - 2 x mu^T) with a *running* min/argmin
carried across center tiles — the same streaming-reduction structure as
flash attention's running softmax.

Grid: (n_blocks, k_blocks); the k axis is the sequential ("arbitrary")
dimension so output tiles are revisited and accumulated in place.
VMEM working set per step: bn*D (points) + bk*D (centers) + bn*bk (distances)
— block defaults keep this well under a v5e core's ~16 MiB VMEM budget with
D up to 8192.

Active-prefix restriction: the pool's valid slots are a prefix (centers are
appended serially), so `k_active` — the pool count, a *traced* scalar passed
as a scalar-prefetch operand — restricts the work to the count-rounded
prefix twice over:

  * compute: `pl.when` skips the kernel body for tiles at or beyond the
    prefix, so skipped tiles do no MXU/VPU work;
  * HBM traffic: the center/mask BlockSpec index maps (which receive the
    prefetched scalar *before* the kernel body runs) clamp the block index
    at the last active tile, so the pipeline re-addresses an
    already-resident block instead of DMAing a dead one — Pallas elides the
    copy when consecutive grid steps map to the same block.

The grid stays static (K_max tiles, JAX needs static shapes) but both the
compute AND the HBM transfer per epoch track the *occupied* pool size
rather than the K_max capacity.

`dpmeans_assign_emulate` is a vmapped jnp re-implementation of the exact
kernel schedule (same tiles, same f32 accumulation, same running-argmin
tie-breaking, same prefix skipping) — the fast stand-in for interpret mode,
whose per-grid-step Python loop is too slow to parity-check production
shapes (serving buckets) in CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MATMUL_PRECISION

__all__ = ["dpmeans_assign", "dpmeans_assign_emulate"]


def _assign_kernel(k_active_ref, x_ref, c_ref, mask_ref, d2_ref, idx_ref, *,
                   bk: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        d2_ref[...] = jnp.full_like(d2_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    # Skip whole center tiles beyond the active prefix: every slot in the
    # tile is masked out anyway, so the running min/argmin cannot change.
    @pl.when(kb * bk < k_active_ref[0])
    def _work():
        x = x_ref[...].astype(jnp.float32)            # (bn, D)
        c = c_ref[...].astype(jnp.float32)            # (bk, D)
        m = mask_ref[...]                             # (1, bk)

        x2 = jnp.sum(x * x, axis=-1, keepdims=True)   # (bn, 1)
        c2 = jnp.sum(c * c, axis=-1)[None, :]         # (1, bk)
        # MXU: the only O(bn*bk*D) term is a single matmul.
        d2 = jnp.maximum(x2 + c2 - 2.0 * jax.lax.dot_general(
            x, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32), 0.0)
        d2 = jnp.where(m != 0, d2, jnp.inf)           # masked-out centers

        loc_min = jnp.min(d2, axis=-1, keepdims=True)               # (bn, 1)
        loc_idx = (jnp.argmin(d2, axis=-1, keepdims=True).astype(jnp.int32)
                   + kb * bk)

        run_min = d2_ref[...]
        run_idx = idx_ref[...]
        better = loc_min < run_min
        d2_ref[...] = jnp.where(better, loc_min, run_min)
        idx_ref[...] = jnp.where(better, loc_idx, run_idx)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def dpmeans_assign(x: jnp.ndarray, centers: jnp.ndarray, mask: jnp.ndarray,
                   count: jnp.ndarray | None = None,
                   block_n: int = 256, block_k: int = 128,
                   interpret: bool = False):
    """Min squared distance and argmin over masked centers.

    x: (N, D), centers: (K, D), mask: (K,) bool.  `count` (traced scalar,
    optional) bounds the valid prefix — center tiles at index >= count are
    skipped entirely (mask must already be False there; the pool invariant
    guarantees it).  Returns (d2min (N,) f32, idx (N,) int32, -1 where no
    valid center).  N, K are padded to block multiples internally.
    """
    n, d = x.shape
    k = centers.shape[0]
    bn = min(block_n, max(8, n))
    bk = min(block_k, max(8, k))
    n_pad = (-n) % bn
    k_pad = (-k) % bk
    if n_pad:
        x = jnp.concatenate([x, jnp.zeros((n_pad, d), x.dtype)], 0)
    if k_pad:
        centers = jnp.concatenate([centers, jnp.zeros((k_pad, d), centers.dtype)], 0)
        mask = jnp.concatenate([mask, jnp.zeros((k_pad,), bool)], 0)
    np_, kp = x.shape[0], centers.shape[0]
    k_active = jnp.full((1,), k if count is None else count, jnp.int32)

    # Scalar-prefetch index map: clamp the center-tile index at the last
    # active tile.  The prefetched count is known before the kernel body,
    # so the pipeline addresses tile min(j, last_active) — a block already
    # in VMEM for every skipped step — and the dead tiles' HBM DMA is
    # elided along with their compute (the `pl.when` in the body).
    def _center_tile(i, j, k_ref):
        last = jnp.maximum((k_ref[0] + bk - 1) // bk, 1) - 1
        return jnp.minimum(j, last), 0

    def _mask_tile(i, j, k_ref):
        return 0, _center_tile(i, j, k_ref)[0]

    grid = (np_ // bn, kp // bk)
    d2, idx = pl.pallas_call(
        functools.partial(_assign_kernel, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bk, d), _center_tile),
                pl.BlockSpec((1, bk), _mask_tile),
            ],
            out_specs=[
                pl.BlockSpec((bn, 1), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bn, 1), lambda i, j, k_ref: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        ],
        interpret=interpret,
    )(k_active, x, centers, mask.astype(jnp.int32)[None, :])
    return d2[:n, 0], idx[:n, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "block_k"))
def dpmeans_assign_emulate(x: jnp.ndarray, centers: jnp.ndarray,
                           mask: jnp.ndarray,
                           count: jnp.ndarray | None = None,
                           block_n: int = 256, block_k: int = 128):
    """Vmapped emulation of the Pallas kernel's exact schedule.

    Same contract as `dpmeans_assign`, computed as vmap-over-n-blocks of a
    scan-over-k-tiles that mirrors the kernel body op for op: identical
    padding/clamping, the same f32 `dot_general` per tile, per-tile argmin
    + running strict-< merge (so cross-tile ties resolve to the lower tile
    exactly as the kernel does), and count-based tile skipping.  Runs as
    ONE compiled XLA computation — no per-grid-step Python — so production
    shapes (serving buckets, large K_max) can be parity-checked in CI where
    interpret mode would take minutes.
    """
    n, d = x.shape
    k = centers.shape[0]
    bn = min(block_n, max(8, n))
    bk = min(block_k, max(8, k))
    n_pad = (-n) % bn
    k_pad = (-k) % bk
    if n_pad:
        x = jnp.concatenate([x, jnp.zeros((n_pad, d), x.dtype)], 0)
    if k_pad:
        centers = jnp.concatenate(
            [centers, jnp.zeros((k_pad, d), centers.dtype)], 0)
        mask = jnp.concatenate([mask, jnp.zeros((k_pad,), bool)], 0)
    k_active = jnp.asarray(k if count is None else count, jnp.int32)

    xb = x.reshape(-1, bn, d)
    cb = centers.reshape(-1, bk, d)
    mb = mask.reshape(-1, bk)
    kbs = jnp.arange(cb.shape[0], dtype=jnp.int32)

    def one_block(xblk):
        xf = xblk.astype(jnp.float32)
        x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)

        def tile(carry, inp):
            run_min, run_idx = carry
            kb, c, m = inp
            cf = c.astype(jnp.float32)
            c2 = jnp.sum(cf * cf, axis=-1)[None, :]
            d2 = jnp.maximum(x2 + c2 - 2.0 * jax.lax.dot_general(
                xf, cf, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
                preferred_element_type=jnp.float32), 0.0)
            d2 = jnp.where(m[None, :], d2, jnp.inf)
            loc_min = jnp.min(d2, axis=-1)
            loc_idx = jnp.argmin(d2, axis=-1).astype(jnp.int32) + kb * bk
            better = jnp.logical_and(loc_min < run_min, kb * bk < k_active)
            return (jnp.where(better, loc_min, run_min),
                    jnp.where(better, loc_idx, run_idx)), None

        init = (jnp.full((bn,), jnp.inf, jnp.float32),
                jnp.full((bn,), -1, jnp.int32))
        (d2m, idxm), _ = jax.lax.scan(tile, init, (kbs, cb, mb))
        return d2m, idxm

    d2, idx = jax.vmap(one_block)(xb)
    return d2.reshape(-1)[:n], idx.reshape(-1)[:n]
