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

The screen (`dpmeans_assign_screen`, `screened_assign`).  At HIGHEST an
f32 distance matmul is six bf16 MXU passes.  Calls of at least
SCREEN_MIN_ROWS rows over more than one center tile (`takes_screen`, a
rule on the static shape) first run a screen with the same grid, clamp
and lane-wide state, whose distances take three passes.  x = x_hi + x_lo
+ r_x and c = c_hi + c_lo + r_c, where each part is a float32 that
bfloat16 holds exactly (`_split`: x once a row block, c a column group),
so that a one-pass (DEFAULT) MXU dot takes it without rounding:

    2s = 2 (x_hi.c_hi + x_hi.c_lo + x_lo.c_hi),     accumulated in f32

(the 2 rides on x, exactly), and S = max((x^2 + c^2) - 2s, 0), masked
centers inf; the clamp, which keeps the order, is applied once a lane at
the end.  Each lane keeps its minimum, its index and its second-smallest
value; the kernel keeps the largest ||c||^2 of the live tiles (c_max^2,
masked slots included: a bound).  At a row's last step, with m its least
screened value and

    E = kappa_D ||x|| c_max + rho_D (||x||^2 + c_max^2)    (`screen_bound`),

the candidates are the lanes whose minimum lies in the band [m, m + 2E],
and the row is certified when at most M lanes are in it and every lane's
second-smallest value lies above it.  A certified row's candidates are
re-scored in full f32 (`_rescore`, the kernel's expression max(x^2 + c^2
- 2 x.c, 0)) and the least wins, lowest index on ties; a call with any
uncertified row runs `dpmeans_assign` itself, under a `lax.cond` on the
device.  The result is the HIGHEST answer either way: the same (d2min,
idx) contract, -1 / inf on an empty pool.  A NaN (garbage in a masked
slot's center, say) certifies no row it reaches, so that call falls back.

Why the band holds the HIGHEST nearest center.  Let u = 2^-8 (bf16) and
v = 2^-24 (f32) be the unit roundoffs, g_n = n v / (1 - n v), and for a
pair p = x.c, P = sum_i |x_i c_i| <= ||x|| ||c|| (Cauchy-Schwarz).

  * Split.  `_round8` rounds to the nearest 8-bit significand, so |x -
    x_hi| <= u|x|, |r_x| <= u^2 |x|, and the three products drop x_lo
    c_lo, x_hi r_c, r_x c and the like: |p - p_3| <= (3u^2 + 6u^3 + 4u^4)
    P.
  * Screen sums.  A product of bf16 values is exact in f32.  Each pass
    sums its D products in some tree (at most D - 1 additions on a
    product's path; the MXU sums 128-deep chunks, then the chunks), and
    the passes are joined by at most 3 ceil(D/128) more (Mosaic chains
    the three dots into one accumulator, chunk by chunk; off the chip two
    additions join them).  The products' magnitudes sum to at most
    (1+u)^2 (1+2u) P, so the error is at most g_(D + 3 ceil(D/128))
    (1+u)^2 (1+2u) P.
  * HIGHEST.  Its result h drops at most 8u^3 P splitting f32 into bf16
    (six passes on the MXU; in plain f32, as the re-score and the CPU
    compute, each product's rounding, v = u^3) and its sums add at most
    g_(D + 6 ceil(D/128)) (1+4u) P, by the same count over six passes.

S and H differ by twice the dot's error:

    kappa_D = 2 (3u^2 + 6u^3 + 4u^4 + (1+u)^2 (1+2u) g_(D + 3 ceil(D/128))
                 + 8u^3 + (1+4u) g_(D + 6 ceil(D/128))).

The norms and the combine add a term in ||x||^2 + ||c||^2: each
evaluation sums x^2 and c^2 in its own order (they differ by at most
2 g_D ||x||^2 and 2 g_D ||c||^2), and the screen's two roundings (x^2 +
c^2, then less 2s), HIGHEST's two and the band's sum m + 2E each lose at
most 2v (||x||^2 + ||c||^2): rho_D = 2 g_D + 12 v.  Both are rounded up
by 2^-10 for the f32 evaluation of E.  So |S - H| <= E for every pair of
a row (the clamp is 1-Lipschitz, and a masked center is inf in both).
If c* is a HIGHEST argmin and c_s the screened one, S(c*) <= H(c*) + E <=
H(c_s) + E <= S(c_s) + 2E = m + 2E: c* lies in the band.  A lane keeps
only its minimum's index; its second-smallest above the band means it
hides no second center in the band, so the in-band lanes' minima are
every center HIGHEST could rank first.  (The model assumes no underflow:
|x_i c_i| above 2^-126.)  For unit rows E = 1.4e-4 at D = 96 and 3.5e-4
at D = 512.

Off the chip the kernels run in interpret mode (`ops.assign(...,
backend="pallas")`), and the tests hold them to `kernels/ref.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MATMUL_PRECISION

__all__ = ["dpmeans_assign", "dpmeans_assign_screen", "screened_assign",
           "takes_screen", "screen_bound", "assign_tiles",
           "assign_tile_steps", "VMEM_BUDGET", "SCREEN_CANDIDATES",
           "SCREEN_MIN_ROWS"]

LANES = 128
MAX_ROWS = 1024
# Bytes of (bn*D + bk*D + bn*bk) f32, double-buffered, a tile may count:
# 12 of the 16 MiB scoped-VMEM default of a v5e core (SCOPED_VMEM), which
# leaves room for the (bn, 128) running state and the outputs.
VMEM_BUDGET = 12 << 20
SCOPED_VMEM = 16 << 20
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


def _tile_maps(bk: int):
    """The center and mask BlockSpec index maps of a (., bk) tiling, with
    the scalar-prefetch clamp: the center-tile index stops at the last
    active tile.  The prefetched count is known before the kernel body,
    so the pipeline addresses tile min(j, last_active) — a block already
    in VMEM for every skipped step — and the dead tiles' HBM DMA is
    elided along with their compute (the `pl.when` in the body)."""
    def center_tile(i, j, k_ref):
        last = jnp.maximum((k_ref[0] + bk - 1) // bk, 1) - 1
        return jnp.minimum(j, last), 0

    def mask_tile(i, j, k_ref):
        return center_tile(i, j, k_ref)[0], 0, 0

    return center_tile, mask_tile


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

    center_tile, mask_tile = _tile_maps(bk)
    lanes = _lanes(bk)
    bg = _group(bn, bk)
    d2, idx = pl.pallas_call(
        functools.partial(_assign_kernel, bk=bk, bg=bg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_ // bn, kp // bk),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, j, k_ref: (i, 0)),
                pl.BlockSpec((bk, d), center_tile),
                pl.BlockSpec((bk // bg, 1, bg), mask_tile),
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


# ------------------------------------------------------------------ screen

# M: candidates a row may carry out of the screen into the re-score.
SCREEN_CANDIDATES = 4
# R: the fewest rows a call takes the screen at (`takes_screen`).
SCREEN_MIN_ROWS = 128
_U = 2.0 ** -8          # bf16 unit roundoff
_V = 2.0 ** -24         # f32 unit roundoff


def _gamma(n: int) -> float:
    return n * _V / (1.0 - n * _V)


def screen_bound(d: int) -> tuple[float, float]:
    """(kappa_D, rho_D): |screened - HIGHEST| <= E for every pair (x, c)
    of a row, E = kappa_D * ||x|| * c_max + rho_D * (||x||^2 + c_max^2),
    c_max the largest ||c|| the screen saw.  The module docstring derives
    both; each is rounded up by 2^-10 to cover the f32 evaluation of E
    and of the band."""
    u = _U
    chunks = _cdiv(d, LANES)
    split = 3 * u**2 + 6 * u**3 + 4 * u**4
    terms = (1 + u) ** 2 * (1 + 2 * u)
    highest = 8 * u**3 + (1 + 4 * u) * _gamma(d + 6 * chunks)
    kappa = 2 * (split + terms * _gamma(d + 3 * chunks) + highest)
    rho = 2 * _gamma(d) + 12 * _V
    pad = 1 + 2.0 ** -10
    return kappa * pad, rho * pad


def _screen_vmem(bn: int, d: int) -> int:
    """The scoped VMEM the screen asks for: the default its tiles were
    sized in (`assign_tiles`), plus what it holds beyond `dpmeans_assign`:
    x_hi and x_lo (f32, bn x D each), the second-minimum state and a third
    output ((bn, 128) words each, the output double-buffered)."""
    return SCOPED_VMEM + bn * d * 4 * 2 + 3 * bn * LANES * 4


def takes_screen(n: int, d: int, k: int) -> bool:
    """Whether an (N, D) x (K, D) call takes the screen: at least
    SCREEN_MIN_ROWS rows, and more than one center tile."""
    return n >= SCREEN_MIN_ROWS and assign_tiles(n, d, k)[1] < k


def _round8(a):
    """a (float32) rounded to its nearest 8-bit significand (ties away from
    zero): a float32 that bfloat16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    return jax.lax.bitcast_convert_type((bits + 0x8000) & -0x10000,
                                        jnp.float32)


def _split(a):
    """a (float32) = hi + lo + r with hi and lo float32 values that
    bfloat16 holds exactly, |a - hi| <= 2^-8 |a| and |r| <= 2^-16 |a|."""
    hi = _round8(a)
    return hi, _round8(a - hi)


def _dot_nt(a, b):
    """a @ b.T in one MXU pass: a and b hold bfloat16 values (`_split`),
    which the pass takes exactly, and it accumulates in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _fold2(a, run_min, run_sec, run_idx, base):
    """`_fold` that also keeps each lane's second-smallest value: a value
    below the minimum pushes the minimum to second place."""
    lanes = run_min.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, run_idx.shape, 1) + base
    for j in range(a.shape[-1] // lanes):
        s = a[:, j * lanes:(j + 1) * lanes]
        run_sec = jnp.minimum(run_sec, jnp.maximum(s, run_min))
        run_idx = jnp.where(s < run_min, lane + j * lanes, run_idx)
        run_min = jnp.minimum(run_min, s)
    return run_min, run_sec, run_idx


def _certify(run_min, run_sec, run_idx, x2, c2max, *, kappa, rho, m):
    """The band test at a row block's last center step.  The running state
    holds x^2 + c^2 - 2 x.c; the clamp at 0, which keeps the order, is
    applied here, once a lane.

    -> (d2 (bn, 1) the screened minimum, cand (bn, 128) int32 with the
    row's candidates ascending in lanes 0..m-1 and -1 elsewhere, cert
    (bn, 1) int32)."""
    lane_min = jnp.maximum(run_min, 0.0)
    lane_sec = jnp.maximum(run_sec, 0.0)
    d2 = jnp.min(lane_min, axis=-1, keepdims=True)
    e = kappa * jnp.sqrt(x2) * jnp.sqrt(c2max) + rho * (x2 + c2max)
    band = d2 + 2.0 * e
    live = (lane_min <= band) & (lane_min < jnp.inf)
    n_live = jnp.sum(live.astype(jnp.float32), axis=-1, keepdims=True)
    sec = jnp.min(lane_sec, axis=-1, keepdims=True)
    cert = (d2 == jnp.inf) | ((n_live <= m) & (sec > band))
    lane = jax.lax.broadcasted_iota(jnp.int32, run_idx.shape, 1)
    cand = jnp.full(run_idx.shape, -1, jnp.int32)
    for j in range(m):
        cj = jnp.min(jnp.where(live, run_idx, _INT_MAX), axis=-1,
                     keepdims=True)
        live = live & (run_idx != cj)
        cand = jnp.where(lane == j, jnp.where(cj == _INT_MAX, -1, cj), cand)
    return d2, cand, cert.astype(jnp.int32)


def _screen_kernel(k_active_ref, x_ref, c_ref, mask_ref, d2_ref, cand_ref,
                   cert_ref, x2_ref, xhi_ref, xlo_ref, run_min_ref,
                   run_sec_ref, run_idx_ref, c2_ref, *, bk: int, bg: int,
                   kappa: float, rho: float, m: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32)
        x2_ref[...] = jnp.sum(x * x, axis=-1, keepdims=True)
        # 2x, so that the dots give 2 x.c: scaling by 2 is exact.
        xhi_ref[...], xlo_ref[...] = _split(2.0 * x)
        run_min_ref[...] = jnp.full_like(run_min_ref, jnp.inf)
        run_sec_ref[...] = jnp.full_like(run_sec_ref, jnp.inf)
        run_idx_ref[...] = jnp.full_like(run_idx_ref, -1)
        c2_ref[...] = jnp.zeros_like(c2_ref)

    @pl.when(kb * bk < k_active_ref[0])
    def _work():
        xhi, xlo = xhi_ref[...], xlo_ref[...]

        def group(g, carry):
            off = pl.multiple_of(g * bg, bg)
            c = c_ref[pl.ds(off, bg), :].astype(jnp.float32)    # (bg, D)
            mk = mask_ref[g]                                     # (1, bg)
            c2 = jnp.sum(c * c, axis=-1)[None, :]                # (1, bg)
            # every center of a live tile, masked or not: a bound
            c2_ref[...] = jnp.maximum(c2_ref[...], jnp.max(c2))
            hi, lo = _split(c)
            # Three bf16 MXU passes, accumulated in f32:
            # 2 (x_hi.c_hi + x_hi.c_lo + x_lo.c_hi).
            two_dot = _dot_nt(xhi, hi) + (_dot_nt(xhi, lo)
                                          + _dot_nt(xlo, hi))
            a = jnp.where(mk != 0, (x2_ref[...] + c2) - two_dot, jnp.inf)
            run = _fold2(a, run_min_ref[...], run_sec_ref[...],
                         run_idx_ref[...], kb * bk + off)
            run_min_ref[...], run_sec_ref[...], run_idx_ref[...] = run
            return carry

        if bk == bg:
            group(0, None)
        else:
            jax.lax.fori_loop(0, bk // bg, group, None)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _reduce():
        d2_ref[...], cand_ref[...], cert_ref[...] = _certify(
            run_min_ref[...], run_sec_ref[...], run_idx_ref[...],
            x2_ref[...], c2_ref[...], kappa=kappa, rho=rho, m=m)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def dpmeans_assign_screen(x: jnp.ndarray, centers: jnp.ndarray,
                          mask: jnp.ndarray,
                          count: jnp.ndarray | None = None,
                          block_n: int | None = None,
                          block_k: int | None = None,
                          interpret: bool = False):
    """The screen: `dpmeans_assign`'s grid, clamp and running state, its
    distances in three bf16 MXU passes.

    Returns (d2 (N,) f32, each row's smallest screened distance, inf where
    no valid center; cand (N, SCREEN_CANDIDATES) int32, the row's
    candidates in ascending index order, -1 padded; certified (N,) bool).
    A certified row's HIGHEST nearest center is among its candidates (none
    where the pool is empty).  Center tiles must be lane multiples.
    """
    n, d = x.shape
    k = centers.shape[0]
    bn, bk = _blocks(n, d, k, block_n, block_k)
    if bk % LANES:
        raise ValueError(f"the screen needs center tiles of a multiple of "
                         f"{LANES}, got {bk}")
    x, centers, mask = _pad(x, centers, mask, bn, bk)
    np_, kp = x.shape[0], centers.shape[0]
    k_active = jnp.full((1,), k if count is None else count, jnp.int32)

    center_tile, mask_tile = _tile_maps(bk)

    def rows(i, j, k_ref):
        return i, 0

    bg = _group(bn, bk)
    kappa, rho = screen_bound(d)
    d2, cand, cert = pl.pallas_call(
        functools.partial(_screen_kernel, bk=bk, bg=bg, kappa=kappa,
                          rho=rho, m=SCREEN_CANDIDATES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_ // bn, kp // bk),
            in_specs=[
                pl.BlockSpec((bn, d), rows),
                pl.BlockSpec((bk, d), center_tile),
                pl.BlockSpec((bk // bg, 1, bg), mask_tile),
            ],
            out_specs=[
                pl.BlockSpec((bn, 1), rows),
                pl.BlockSpec((bn, LANES), rows),
                pl.BlockSpec((bn, 1), rows),
            ],
            scratch_shapes=[
                pltpu.VMEM((bn, 1), jnp.float32),       # ||x||^2
                pltpu.VMEM((bn, d), jnp.float32),       # 2 x_hi
                pltpu.VMEM((bn, d), jnp.float32),       # 2 x_lo
                pltpu.VMEM((bn, LANES), jnp.float32),   # running min
                pltpu.VMEM((bn, LANES), jnp.float32),   # running second
                pltpu.VMEM((bn, LANES), jnp.int32),     # running argmin
                pltpu.VMEM((1, 1), jnp.float32),        # max ||c||^2 seen
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, LANES), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_screen_vmem(bn, d)),
        interpret=interpret,
    )(k_active, x, centers, mask.astype(jnp.int32).reshape(-1, 1, bg))
    return d2[:n, 0], cand[:n, :SCREEN_CANDIDATES], cert[:n, 0] != 0


def _rescore(x, centers, cand):
    """Full-f32 distances to each row's candidates, the kernel's expression
    max(x^2 + c^2 - 2 x.c, 0) with every product and sum in f32 (what
    HIGHEST computes, by elementwise work: a few candidates a row make a
    poor matmul); the least, lowest index on ties (candidates ascend);
    (inf, -1) where a row has none."""
    xf = x.astype(jnp.float32)
    g = jnp.take(centers, jnp.maximum(cand, 0), axis=0).astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)
    c2 = jnp.sum(g * g, axis=-1)
    dot = jnp.sum(xf[:, None, :] * g, axis=-1)
    d2 = jnp.where(cand >= 0, jnp.maximum(x2 + c2 - 2.0 * dot, 0.0),
                   jnp.inf)
    d2min = jnp.min(d2, axis=-1)
    idx = jnp.min(jnp.where(d2 == d2min[:, None], cand, _INT_MAX), axis=-1)
    return d2min, jnp.where(jnp.isfinite(d2min), idx, -1)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def screened_assign(x: jnp.ndarray, centers: jnp.ndarray, mask: jnp.ndarray,
                    count: jnp.ndarray | None = None,
                    block_n: int | None = None, block_k: int | None = None,
                    interpret: bool = False):
    """`dpmeans_assign`'s contract through the screen: the screen, the
    full-f32 re-score of every row's candidates, and, where any row of the
    call is not certified, `dpmeans_assign` itself over the whole call
    instead (a `lax.cond` on the device).  `block_n`/`block_k` override
    both kernels' tiles."""
    blocks = dict(block_n=block_n, block_k=block_k, interpret=interpret)
    _, cand, cert = dpmeans_assign_screen(x, centers, mask, count=count,
                                          **blocks)
    return jax.lax.cond(
        jnp.all(cert),
        lambda: _rescore(x, centers, cand),
        lambda: dpmeans_assign(x, centers, mask, count=count, **blocks))
