"""Pallas TPU kernel for the OCC hot loop: pairwise sq-distance + argmin.

TPU adaptation of the paper's `argmin_{mu in C} ||x - mu||` (DESIGN.md §6/§9):
instead of a GPU-style point-per-thread gather, the distance matrix block is
an MXU matmul (||x||^2 + ||mu||^2 - 2 x mu^T) with a *running* min/argmin
carried across center tiles — the same streaming-reduction structure as
flash attention's running softmax.

Grid: (n_blocks, k_blocks); the k axis is the sequential ("arbitrary")
dimension so output tiles are revisited and accumulated in place.  The
tiles come from the shape (`assign_tiles`): one row block for up to 1024
rows — so an epoch's centers stream from HBM once — and the widest
power-of-two multiple of 128 centers whose working set, bn*D (points) +
bk*D (centers) + bn*bk (distances), double-buffered, fits `VMEM_BUDGET`.
Fixed per-step cost is then paid once per wide tile, not once per 128
centers.  Inside a step a loop walks the tile in column groups of at most
GROUP_ELEMS distances, which bounds the unrolled body the compiler emits.

Per-row-block work that does not depend on the centers is done once, at
the first center step: ||x||^2 goes to VMEM scratch.  The running min and
argmin are lane-wide, (bn, 128) in VMEM scratch: each tile folds into them
one 128-lane slice at a time with elementwise compare/select (strict `<`,
slices in ascending column order, so each lane keeps its lowest index of
its minimum).  The one cross-lane reduction runs at the last center step:
d2 = min over lanes, idx = the least index among lanes holding d2 — the
lowest index wins ties, exactly as a global first-occurrence argmin.

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
rather than the K_max capacity; `assign_tile_steps` counts both.

Off the chip the kernel runs in interpret mode (`ops.assign(...,
backend="pallas")`), and the tests hold it to `kernels/ref.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MATMUL_PRECISION

__all__ = ["dpmeans_assign", "assign_tiles", "assign_tile_steps",
           "VMEM_BUDGET"]

LANES = 128
MAX_ROWS = 1024
# Bytes of (bn*D + bk*D + bn*bk) f32, double-buffered, a tile may count:
# 12 of the 16 MiB scoped-VMEM default of a v5e core, which leaves room
# for the (bn, 128) running state and the outputs.
VMEM_BUDGET = 12 << 20
# Distances one step of the loop over a tile's column groups computes.
# The compiler unrolls the loop body over its vregs, so code size and
# compile time follow the group's area, not the tile's: a whole 256 x 4096
# tile at D=96 took 7.0 s to compile for a v5e, in groups of 512 1.3 s.
GROUP_ELEMS = 256 * 512
_INT_MAX = jnp.iinfo(jnp.int32).max


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tile_bytes(bn: int, bk: int, d: int) -> int:
    return (bn * d + bk * d + bn * bk) * 4 * 2


def assign_tiles(n: int, d: int, k: int) -> tuple[int, int]:
    """(bn, bk): the kernel's row block and center tile for (N, D, K).

    bn is all N rows up to MAX_ROWS (more rows split into equal blocks of
    at most MAX_ROWS, multiples of 8); bk the widest 128 * 2^j, at most K,
    whose `_tile_bytes` fit VMEM_BUDGET — a power of two so it divides the
    power-of-two pool capacities without padding.  Where even a 128-wide
    tile does not fit (very wide D), the row block halves until it does.
    K below one lane width takes the whole K in one tile.
    """
    blocks = _cdiv(n, MAX_ROWS)
    bn = max(8, n) if blocks == 1 else 8 * _cdiv(_cdiv(n, blocks), 8)
    while bn > 8 and _tile_bytes(bn, LANES, d) > VMEM_BUDGET:
        bn = 8 * _cdiv(bn // 2, 8)
    if k < LANES:
        return bn, max(8, k)
    bk = LANES
    while 2 * bk <= k and _tile_bytes(bn, 2 * bk, d) <= VMEM_BUDGET:
        bk *= 2
    return bn, bk


def assign_tile_steps(count: int, n: int, d: int, k: int
                      ) -> tuple[int, int]:
    """(grid steps, live steps) of one default-tiled kernel call.

    Grid steps are every (row block, center tile) pair of the static grid;
    live steps those whose body runs — center tiles that start below
    `count` — ceil(count / bk) per row block.  The rest pay only the
    pipeline's fixed cost (their DMA is elided by the index-map clamp).
    """
    bn, bk = assign_tiles(n, d, k)
    row_blocks = _cdiv(n, bn)
    return row_blocks * _cdiv(k, bk), row_blocks * _cdiv(count, bk)


def _blocks(n, d, k, block_n, block_k):
    bn, bk = assign_tiles(n, d, k)
    if block_n is not None:
        bn = min(block_n, max(8, n))
    if block_k is not None:
        bk = min(block_k, max(8, k))
    return bn, bk


def _pad(x, centers, mask, bn, bk):
    n, d = x.shape
    k = centers.shape[0]
    n_pad = (-n) % bn
    k_pad = (-k) % bk
    if n_pad:
        x = jnp.concatenate([x, jnp.zeros((n_pad, d), x.dtype)], 0)
    if k_pad:
        centers = jnp.concatenate(
            [centers, jnp.zeros((k_pad, d), centers.dtype)], 0)
        mask = jnp.concatenate([mask, jnp.zeros((k_pad,), bool)], 0)
    return x, centers, mask


def _fold(d2, run_min, run_idx, base):
    """Fold a (bn, w) block of distances into the lane-wide running state,
    one lane-width slice at a time, in ascending column order; `base` is
    the block's first column index.  Strict `<`: a lane keeps its lowest
    index of its minimum."""
    lanes = run_min.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, run_idx.shape, 1) + base
    for j in range(d2.shape[-1] // lanes):
        s = d2[:, j * lanes:(j + 1) * lanes]
        better = s < run_min
        run_min = jnp.where(better, s, run_min)
        run_idx = jnp.where(better, lane + j * lanes, run_idx)
    return run_min, run_idx


def _finish(run_min, run_idx):
    """The one cross-lane reduction: min over lanes, and the least index
    among the lanes that hold it (-1 where every lane is still empty)."""
    d2 = jnp.min(run_min, axis=-1, keepdims=True)
    idx = jnp.min(jnp.where(run_min == d2, run_idx, _INT_MAX), axis=-1,
                  keepdims=True)
    return d2, idx


def _lanes(bk: int) -> int:
    return LANES if bk % LANES == 0 else bk


def _group(bn: int, bk: int) -> int:
    """Centers per step of the kernel's loop over one tile: the widest
    lane multiple dividing bk with bn * bg <= GROUP_ELEMS, and at least
    one lane width."""
    lanes = _lanes(bk)
    bg = lanes
    while bk % (2 * bg) == 0 and bn * 2 * bg <= GROUP_ELEMS:
        bg *= 2
    return bg


def _assign_kernel(k_active_ref, x_ref, c_ref, mask_ref, d2_ref, idx_ref,
                   x2_ref, run_min_ref, run_idx_ref, *, bk: int,
                   bg: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32)
        x2_ref[...] = jnp.sum(x * x, axis=-1, keepdims=True)   # (bn, 1)
        run_min_ref[...] = jnp.full_like(run_min_ref, jnp.inf)
        run_idx_ref[...] = jnp.full_like(run_idx_ref, -1)

    # Skip whole center tiles beyond the active prefix: every slot in the
    # tile is masked out anyway, so the running min/argmin cannot change.
    @pl.when(kb * bk < k_active_ref[0])
    def _work():
        x = x_ref[...].astype(jnp.float32)            # (bn, D)

        def group(g, carry):
            off = pl.multiple_of(g * bg, bg)
            c = c_ref[pl.ds(off, bg), :].astype(jnp.float32)    # (bg, D)
            m = mask_ref[g]                                      # (1, bg)
            c2 = jnp.sum(c * c, axis=-1)[None, :]                # (1, bg)
            # MXU: the only O(bn*bg*D) term is a single matmul.
            d2 = jnp.maximum(x2_ref[...] + c2 - 2.0 * jax.lax.dot_general(
                x, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
                preferred_element_type=jnp.float32), 0.0)
            d2 = jnp.where(m != 0, d2, jnp.inf)       # masked-out centers
            run_min, run_idx = _fold(d2, run_min_ref[...], run_idx_ref[...],
                                     kb * bk + off)
            run_min_ref[...] = run_min
            run_idx_ref[...] = run_idx
            return carry

        if bk == bg:
            group(0, None)
        else:
            jax.lax.fori_loop(0, bk // bg, group, None)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _reduce():
        d2, idx = _finish(run_min_ref[...], run_idx_ref[...])
        d2_ref[...] = d2
        idx_ref[...] = idx


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def dpmeans_assign(x: jnp.ndarray, centers: jnp.ndarray, mask: jnp.ndarray,
                   count: jnp.ndarray | None = None,
                   block_n: int | None = None, block_k: int | None = None,
                   interpret: bool = False):
    """Min squared distance and argmin over masked centers.

    x: (N, D), centers: (K, D), mask: (K,) bool.  `count` (traced scalar,
    optional) bounds the valid prefix — center tiles at index >= count are
    skipped entirely (mask must already be False there; the pool invariant
    guarantees it).  Returns (d2min (N,) f32, idx (N,) int32, -1 where no
    valid center; ties go to the lowest index).  Tiles default to
    `assign_tiles(N, D, K)`; `block_n`/`block_k` override them.  N, K are
    padded to block multiples internally.
    """
    n, d = x.shape
    k = centers.shape[0]
    bn, bk = _blocks(n, d, k, block_n, block_k)
    x, centers, mask = _pad(x, centers, mask, bn, bk)
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
        return _center_tile(i, j, k_ref)[0], 0, 0

    lanes = _lanes(bk)
    bg = _group(bn, bk)
    d2, idx = pl.pallas_call(
        functools.partial(_assign_kernel, bk=bk, bg=bg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_ // bn, kp // bk),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bk, d), _center_tile),
                pl.BlockSpec((bk // bg, 1, bg), _mask_tile),
            ],
            out_specs=[
                pl.BlockSpec((bn, 1), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bn, 1), lambda i, j, k_ref: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bn, 1), jnp.float32),       # ||x||^2
                pltpu.VMEM((bn, lanes), jnp.float32),   # running min
                pltpu.VMEM((bn, lanes), jnp.int32),     # running argmin
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        ],
        interpret=interpret,
    )(k_active, x, centers, mask.astype(jnp.int32).reshape(-1, 1, bg))
    return d2[:n, 0], idx[:n, 0]
