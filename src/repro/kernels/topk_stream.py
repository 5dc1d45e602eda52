"""Pallas TPU kernels for streaming serve-time top-k (DESIGN.md §16).

The retrieval-serving hot path: k nearest centers per query at center
counts where materializing the full (B, K) distance matrix is the cost.
Same streaming-reduction shape as `dpmeans_assign` (and flash attention's
running softmax), with the running scalar min generalized to a running
top-k candidate buffer:

  * Grid (n_blocks, k_tiles); the tile axis is sequential, so the (bn, k)
    output block is revisited and merged in place.  No (bn, K) row ever
    exists — VMEM holds bn*D (queries) + bk*D (one center tile) + bn*bk
    (one distance tile) + 2*bn*k (candidates).
  * Per tile: ONE f32 MXU matmul produces the (bn, bk) distance tile, then
    `ref.topk_merge_ref` folds it into the running candidates — k unrolled
    lexicographic-(d2, id) extraction steps over (bn, k + bk).  The merge
    is O(k*(k+bk)) VPU work per row against O(bk*D) MXU work for the tile,
    so for k << D the matmul still dominates (cost model in §16).
  * Active-prefix DMA skip: the center count rides in as a scalar-prefetch
    operand.  `pl.when` skips dead tiles' compute, and the BlockSpec index
    maps clamp the tile index at the last active tile so the pipeline
    re-addresses a block already resident in VMEM — Pallas elides the copy
    when consecutive grid steps map to the same block, so tiles beyond the
    active prefix issue ZERO HBM loads.  `center_tile` is that clamp, the
    one function both the index maps and the host-side count
    `topk_tile_loads` evaluate.

`topk_multiprobe_stream` is the two-level variant serving hierarchical
snapshots (serving/snapshot.build_hier): the scalar-prefetch operands are
the microbatch's probed-cell union (packed ascending) plus its length, and
the center-tile index map reads `cells_ref[j]` — the GATHER HAPPENS IN THE
INDEX MAP, so unprobed shards never leave HBM at all; there is no
materialized (U, S, D) gather buffer.  A per-(query, cell) `member` mask
restricts each query to its own probed cells, which keeps the union
computation microbatch-shared (a requirement: only shared 2-D matmuls are
bitwise-reproducible against the flat kernel — DESIGN.md §16).

Selection is by lexicographic (d2, original id), which equals
`lax.top_k`'s lower-index-first tie order and is invariant to candidate
tiling/ordering — so for f32 inputs flat kernel == multiprobe kernel ==
`ref.topk_ref` bit-exactly (the D-contraction is never split, so even the
distances are bitwise equal), across every block size.  Off the chip both
kernels run in interpret mode (`ops.serve_topk(..., backend="pallas")`),
and the tests hold them to `kernels/ref.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MATMUL_PRECISION, TOPK_SENTINEL, topk_merge_ref

__all__ = ["topk_stream", "topk_multiprobe_stream", "topk_tile_loads"]


def center_tile(j, count, bk: int, xp=jnp):
    """The center tile grid step j reads: j, clamped at the last tile that
    starts below `count` (tile 0 for an empty prefix).  `xp` is jnp in the
    kernel's index map, where `count` is the prefetched scalar, and numpy
    in `topk_tile_loads`, which walks the same map on the host."""
    return xp.minimum(j, xp.maximum((count + bk - 1) // bk, 1) - 1)


def topk_tile_loads(count: int, k_total: int, block_k: int = 128) -> int:
    """Center-tile HBM loads one row-block sweep of the flat kernel issues.

    Walks the kernel's own index map (`center_tile`) over the grid: the
    pipeline DMAs a block only when the mapped index changes between
    consecutive grid steps, so loads == the number of distinct consecutive
    mapped indices == max(1, ceil(count/bk)) — tiles beyond the active
    prefix contribute zero.
    """
    bk = min(block_k, max(8, k_total))
    mapped = center_tile(np.arange(-(-k_total // bk)), count, bk, np)
    return 1 + int(np.count_nonzero(np.diff(mapped)))


def _finalize(d2, idx):
    """Shared post-pass: exhausted candidate slots surface as (inf, -1)."""
    return d2, jnp.where(jnp.isfinite(d2), idx, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Flat streaming kernel
# ---------------------------------------------------------------------------

def _topk_kernel(k_active_ref, x_ref, c_ref, mask_ref, d2_ref, idx_ref, *,
                 bk: int, kk: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        d2_ref[...] = jnp.full_like(d2_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, TOPK_SENTINEL)

    @pl.when(kb * bk < k_active_ref[0])
    def _work():
        x = x_ref[...].astype(jnp.float32)            # (bn, D)
        c = c_ref[...].astype(jnp.float32)            # (bk, D)
        m = mask_ref[...]                             # (1, bk)
        bn = x.shape[0]

        x2 = jnp.sum(x * x, axis=-1, keepdims=True)
        c2 = jnp.sum(c * c, axis=-1)[None, :]
        d2 = jnp.maximum(x2 + c2 - 2.0 * jax.lax.dot_general(
            x, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32), 0.0)
        d2 = jnp.where(m != 0, d2, jnp.inf)
        ids = (jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1) + kb * bk)

        nd, ni = topk_merge_ref(d2_ref[...], idx_ref[...], d2, ids, kk)
        d2_ref[...] = nd
        idx_ref[...] = ni


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "block_k", "interpret"))
def topk_stream(x: jnp.ndarray, centers: jnp.ndarray, mask: jnp.ndarray,
                k: int, count: jnp.ndarray | None = None,
                block_n: int = 256, block_k: int = 128,
                interpret: bool = False):
    """k nearest centers, streamed: (d2 (N, k) f32 ascending, idx (N, k)).

    x (N, D), centers (K, D), mask (K,) bool, `count` an optional traced
    scalar bounding the valid prefix (tiles at/after it skip compute AND
    HBM DMA).  Ties break by lower index; exhausted slots are (inf, -1).
    k is a compile-time constant and should stay small (the merge unrolls
    k extraction steps).  k may exceed K — the tail comes back exhausted.
    """
    n, d = x.shape
    kc = centers.shape[0]
    bn = min(block_n, max(8, n))
    bk = min(block_k, max(8, kc))
    n_pad = (-n) % bn
    k_pad = (-kc) % bk
    if n_pad:
        x = jnp.concatenate([x, jnp.zeros((n_pad, d), x.dtype)], 0)
    if k_pad:
        centers = jnp.concatenate(
            [centers, jnp.zeros((k_pad, d), centers.dtype)], 0)
        mask = jnp.concatenate([mask, jnp.zeros((k_pad,), bool)], 0)
    np_, kp = x.shape[0], centers.shape[0]
    k_active = jnp.full((1,), kc if count is None else count, jnp.int32)

    def _center_tile(i, j, k_ref):
        return center_tile(j, k_ref[0], bk), 0

    def _mask_tile(i, j, k_ref):
        return 0, center_tile(j, k_ref[0], bk)

    grid = (np_ // bn, kp // bk)
    d2, idx = pl.pallas_call(
        functools.partial(_topk_kernel, bk=bk, kk=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bk, d), _center_tile),
                pl.BlockSpec((1, bk), _mask_tile),
            ],
            out_specs=[
                pl.BlockSpec((bn, k), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bn, k), lambda i, j, k_ref: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((np_, k), jnp.float32),
            jax.ShapeDtypeStruct((np_, k), jnp.int32),
        ],
        interpret=interpret,
    )(k_active, x, centers, mask.astype(jnp.int32)[None, :])
    d2, idx = _finalize(d2, idx)
    return d2[:n], idx[:n]


# ---------------------------------------------------------------------------
# Two-level multi-probe kernel (hierarchical snapshots)
# ---------------------------------------------------------------------------

def _mp_kernel(u_count_ref, cells_ref, x_ref, f_ref, ids_ref, fmask_ref,
               member_ref, d2_ref, idx_ref, *, kk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        d2_ref[...] = jnp.full_like(d2_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, TOPK_SENTINEL)

    @pl.when(j < u_count_ref[0])
    def _work():
        x = x_ref[...].astype(jnp.float32)            # (bn, D)
        c = f_ref[0].astype(jnp.float32)              # (S, D) — one shard
        ids = ids_ref[0]                              # (1, S)
        fm = fmask_ref[0]                             # (1, S)
        # Membership of union slot j: column j of the row block's (bn, U)
        # membership, picked by a lane mask (U is small — at most n_cells).
        mem_all = member_ref[...]                     # (bn, U)
        col = jax.lax.broadcasted_iota(jnp.int32, mem_all.shape, 1) == j
        mem = jnp.max(jnp.where(col, mem_all, 0), axis=1, keepdims=True)

        x2 = jnp.sum(x * x, axis=-1, keepdims=True)
        c2 = jnp.sum(c * c, axis=-1)[None, :]
        d2 = jnp.maximum(x2 + c2 - 2.0 * jax.lax.dot_general(
            x, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32), 0.0)
        d2 = jnp.where((fm != 0) & (mem != 0), d2, jnp.inf)

        nd, ni = topk_merge_ref(
            d2_ref[...], idx_ref[...], d2,
            jnp.broadcast_to(ids, d2.shape), kk)
        d2_ref[...] = nd
        idx_ref[...] = ni


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def topk_multiprobe_stream(x: jnp.ndarray, fine: jnp.ndarray,
                           fine_ids: jnp.ndarray, fine_mask: jnp.ndarray,
                           cells: jnp.ndarray, member: jnp.ndarray, k: int,
                           u_count: jnp.ndarray | None = None,
                           block_n: int = 256, interpret: bool = False):
    """Stream ONLY the probed fine shards: (d2 (B, k) f32, idx (B, k)).

    fine (n_cells, S, D) / fine_ids / fine_mask per build_hier; cells (U,)
    the probed-cell union (packed ascending, -1 pad — entries are clamped,
    membership must already be False there); member (B, U); `u_count` the
    traced number of real union entries.  Grid is (B/bn, U) with ONE shard
    per tile; the shard index map reads `cells_ref[j]` — the gather lives
    in the index map, so unprobed shards are never DMAd and tiles past
    `u_count` re-address the resident block (zero HBM loads), exactly the
    flat kernel's prefix clamp with the union as the prefix.
    """
    b, d = x.shape
    s = fine.shape[1]
    u = cells.shape[0]
    bn = min(block_n, max(8, b))
    b_pad = (-b) % bn
    if b_pad:
        x = jnp.concatenate([x, jnp.zeros((b_pad, d), x.dtype)], 0)
        member = jnp.concatenate(
            [member, jnp.zeros((b_pad, u), bool)], 0)
    bp = x.shape[0]
    u_active = jnp.full((1,), u if u_count is None else u_count, jnp.int32)
    cells_cl = jnp.maximum(cells, 0).astype(jnp.int32)

    def _shard_tile(i, j, u_ref, cells_ref):
        jc = jnp.minimum(j, jnp.maximum(u_ref[0], 1) - 1)
        return cells_ref[jc], 0, 0

    grid = (bp // bn, u)
    d2, idx = pl.pallas_call(
        functools.partial(_mp_kernel, kk=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j, u_ref, cells_ref: (i, 0)),
                pl.BlockSpec((1, s, d), _shard_tile),
                pl.BlockSpec((1, 1, s), _shard_tile),
                pl.BlockSpec((1, 1, s), _shard_tile),
                pl.BlockSpec((bn, u), lambda i, j, u_ref, cells_ref: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bn, k), lambda i, j, u_ref, cells_ref: (i, 0)),
                pl.BlockSpec((bn, k), lambda i, j, u_ref, cells_ref: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ],
        interpret=interpret,
    )(u_active, cells_cl, x, fine, fine_ids[:, None, :],
      fine_mask.astype(jnp.int32)[:, None, :], member.astype(jnp.int32))
    d2, idx = _finalize(d2, idx)
    return d2[:b], idx[:b]
