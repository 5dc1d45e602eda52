"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each `<name>_ref` is the semantic spec; kernel sweep tests assert_allclose
against these across shapes and dtypes.

The distance contract every backend is held to, here and on the chip:
candidate ids are EXACT and squared distances agree to `D2_RTOL` /
`D2_ATOL`.  Two computations of one distance may differ in the last ulps
(matmul tiling, accumulation order, a different compiler), so distances
are never compared bitwise across backends or shapes.  The one exception
to exact ids is a near-tie: candidates whose reference distances lie
within the tolerance of each other have no defined order, so their ids
may trade places (`topk_disagreements`).
"""
from __future__ import annotations

import jax.numpy as jnp
import jax
import numpy as np

__all__ = ["assign_ref", "pairwise_argmin_ref", "topk_ref",
           "topk_merge_ref", "topk_multiprobe_ref", "TOPK_SENTINEL",
           "MATMUL_PRECISION", "D2_RTOL", "D2_ATOL", "topk_disagreements",
           "flash_attention_ref", "rmsnorm_ref", "swiglu_ref"]

# Every distance matmul of the OCC path -- the Pallas kernels, these
# oracles and the validator's precompute -- contracts at full f32
# precision.  On TPU an f32 matmul at the default precision is one
# bf16 pass, so the propose and the validator would see different
# distances for the same pair, which breaks the serial equivalence of the
# OCC pass (paper Thm 3.1).  The CPU computes f32 either way.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST

D2_RTOL = 1e-5
D2_ATOL = 1e-5

# Invalid-candidate id inside the top-k selection: larger than any real
# center index, so the lexicographic (d2, id) order pushes exhausted slots
# last deterministically.  Callers map it to -1 wherever d2 is non-finite.
TOPK_SENTINEL = 2**31 - 1


def _matmul_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a @ b.T at `MATMUL_PRECISION`."""
    return jnp.matmul(a, b.T, precision=MATMUL_PRECISION)


def topk_disagreements(d2, idx, d2_ref, idx_ref) -> int:
    """How many (row, rank) slots of a top-k answer break the distance
    contract against a reference top-k of the same queries.

    A slot breaks it when its distance misses the reference distance at
    that rank by more than D2_ATOL + D2_RTOL * |ref| (exhausted slots must
    both be inf), or when its id differs from the reference id without a
    near-tie: the id must sit in the reference row at a rank whose
    distance is within that tolerance of this rank's.  `d2_ref`/`idx_ref`
    may hold more columns than `d2`/`idx` (the next ranks), so a near-tie
    across the k-th rank counts as a tie too.
    """
    d2, idx = np.asarray(d2), np.asarray(idx)
    d2_ref, idx_ref = np.asarray(d2_ref), np.asarray(idx_ref)
    k = d2.shape[1]
    tol = D2_ATOL + D2_RTOL * np.abs(np.where(np.isfinite(d2_ref), d2_ref, 0))
    dr = d2_ref[:, :k]
    both_inf = np.isinf(d2) & np.isinf(dr)
    bad = ~(both_inf | (np.abs(d2 - dr) <= tol[:, :k]))
    for r, j in zip(*np.nonzero(idx != idx_ref[:, :k])):
        at = np.nonzero(idx_ref[r] == idx[r, j])[0]
        if at.size == 0 or abs(d2_ref[r, at[0]] - dr[r, j]) > tol[r, j]:
            bad[r, j] = True
    return int(bad.sum())


def assign_ref(x: jnp.ndarray, centers: jnp.ndarray, mask: jnp.ndarray):
    """`ops.assign` oracle: masked min sq-distance + argmin, idx = -1 where
    no valid center.  Computes IN THE INPUT DTYPE (same expanded-matmul
    algebra as core.objective.sq_dists) so routing `nearest_center` through
    it preserves the propose phase's dtype/precision contract exactly."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=-1)[None, :]
    d2 = jnp.maximum(x2 + c2 - 2.0 * _matmul_t(x, centers), 0.0)
    d2 = jnp.where(mask[None, :], d2, jnp.inf)
    d2min = jnp.min(d2, axis=-1)
    idx = jnp.where(jnp.isfinite(d2min),
                    jnp.argmin(d2, axis=-1), -1).astype(jnp.int32)
    return d2min, idx


def pairwise_argmin_ref(x: jnp.ndarray, centers: jnp.ndarray,
                        mask: jnp.ndarray | None = None):
    """Min squared distance + argmin over centers.  x (N,D), centers (K,D).
    Computes in float32 (matching the Pallas kernel's accumulation dtype)."""
    xf = x.astype(jnp.float32)
    cf = centers.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)
    c2 = jnp.sum(cf * cf, axis=-1)[None, :]
    d2 = jnp.maximum(x2 + c2 - 2.0 * _matmul_t(xf, cf), 0.0)
    if mask is not None:
        d2 = jnp.where(mask[None, :], d2, jnp.inf)
    return jnp.min(d2, axis=-1), jnp.argmin(d2, axis=-1).astype(jnp.int32)


def topk_ref(x: jnp.ndarray, centers: jnp.ndarray, k: int,
             mask: jnp.ndarray | None = None):
    """k nearest centers per query: (d2 (N, k) ascending, idx (N, k) int32).

    Same input-dtype expanded-matmul algebra as `assign_ref` (so the top-1
    column is bit-identical to `assign_ref`'s verdict); slots beyond the
    valid set come back as (inf, -1).  `lax.top_k` breaks distance ties by
    lower index — matching `argmin`, so topk[...,:1] == assign exactly.

    Scoring is restricted to the masked active prefix at the SOURCE: rows
    outside the mask are zeroed before the matmul, so NaN/inf garbage in
    padded pool slots (stale payloads past `count`, snapshot capacity
    padding) cannot poison the distance matrix or the top-k sort order —
    invalid slots are (inf, -1) by construction, never by luck.  For valid
    columns the algebra is untouched (zeroing only changes columns the
    inf-mask overwrites anyway), preserving the top1 == assign contract.
    """
    if mask is not None:
        centers = jnp.where(mask[:, None], centers, 0)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=-1)[None, :]
    d2 = jnp.maximum(x2 + c2 - 2.0 * _matmul_t(x, centers), 0.0)
    if mask is not None:
        d2 = jnp.where(mask[None, :], d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    d2k = -neg
    idx = jnp.where(jnp.isfinite(d2k), idx, -1).astype(jnp.int32)
    return d2k, idx


def topk_merge_ref(run_d: jnp.ndarray, run_i: jnp.ndarray,
                   d2: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Running top-k merge by lexicographic (d2, id) — THE selection spec.

    run_d/run_i: (N, k) current candidates (pad: (inf, TOPK_SENTINEL)).
    d2/ids:      (N, M) new candidates (invalid: d2=inf, any id).
    Returns the new (N, k), ascending by (d2, id): k unrolled extraction
    steps, each taking the distance minimum and, among ties, the smallest
    id — exactly `lax.top_k`'s lower-index-first tie order when ids are
    the candidates' original positions.  Because selection depends only on
    the candidate (value, id) MULTISET, the result is invariant to how
    callers tile or reorder candidates — the property that makes the
    streaming kernel (kernels/topk_stream.py) and the gathered
    multi-probe path both bit-identical to `topk_ref` for f32
    inputs, whatever their block sizes.  (inf, TOPK_SENTINEL) pads are a
    fixed point of the extraction (consuming one re-creates it), so ragged
    candidate sets need no special casing.  Used as the merge body INSIDE
    the Pallas kernel as well — keeping the oracle and the kernel on one
    implementation is what turns parity into a construction, not a test.
    """
    cat_d = jnp.concatenate([run_d, d2], axis=1)
    cat_i = jnp.concatenate([run_i, ids], axis=1)
    out_d, out_i = [], []
    for _ in range(k):
        dmin = jnp.min(cat_d, axis=1)
        tie = cat_d == dmin[:, None]
        imin = jnp.min(jnp.where(tie, cat_i, TOPK_SENTINEL), axis=1)
        out_d.append(dmin)
        out_i.append(imin)
        hit = tie & (cat_i == imin[:, None])
        cat_d = jnp.where(hit, jnp.inf, cat_d)
        cat_i = jnp.where(hit, TOPK_SENTINEL, cat_i)
    return (jnp.concatenate([d[:, None] for d in out_d], axis=1),
            jnp.concatenate([i[:, None] for i in out_i], axis=1))


def topk_multiprobe_ref(x: jnp.ndarray, fine: jnp.ndarray,
                        fine_ids: jnp.ndarray, fine_mask: jnp.ndarray,
                        cells: jnp.ndarray, member: jnp.ndarray, k: int):
    """Multi-probe top-k oracle over a two-level (cell → shard) layout.

    x (B, D); fine (n_cells, S, D) shard buffers; fine_ids/fine_mask
    (n_cells, S) original flat indices (-1 pad) / validity; cells (U,)
    int32 — the microbatch's probed-cell union, packed ascending, -1 pad;
    member (B, U) bool — query b may see candidates of cells[u].

    The distance computation deliberately gathers the probed shards into
    ONE (U*S, D) row matrix and runs a single 2-D matmul shared by the
    whole microbatch: on XLA a row-gathered matmul is bitwise-equal to the
    corresponding columns of the flat `x @ centers.T` (per-query batched
    einsums are NOT), and selection is by (d2, original id) — so when the
    union covers every active cell and member is all-true, the result is
    bit-identical to `topk_ref` on the flat buffers, tie order included.
    Masked shard rows are zeroed before the matmul (same NaN/inf guard as
    `topk_ref`); per-query membership only ever masks AFTER the matmul,
    so it cannot perturb surviving columns.
    """
    s = fine.shape[1]
    u = cells.shape[0]
    cc = jnp.maximum(cells, 0)
    g = jnp.take(fine, cc, axis=0).reshape(u * s, -1)
    gids = jnp.take(fine_ids, cc, axis=0).reshape(u * s)
    gmask = (jnp.take(fine_mask, cc, axis=0).reshape(u * s)
             & jnp.repeat(cells >= 0, s))
    g = jnp.where(gmask[:, None], g, 0)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    g2 = jnp.sum(g * g, axis=-1)[None, :]
    d2 = jnp.maximum(x2 + g2 - 2.0 * _matmul_t(x, g), 0.0)
    ok = gmask[None, :] & jnp.repeat(member, s, axis=1)
    d2 = jnp.where(ok, d2, jnp.inf)
    init_d = jnp.full((x.shape[0], k), jnp.inf, d2.dtype)
    init_i = jnp.full((x.shape[0], k), TOPK_SENTINEL, jnp.int32)
    d2k, idx = topk_merge_ref(init_d, init_i, d2,
                              jnp.broadcast_to(gids[None, :], d2.shape), k)
    idx = jnp.where(jnp.isfinite(d2k), idx, -1).astype(jnp.int32)
    return d2k, idx


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, scale: float | None = None):
    """Reference attention.  q (B,H,S,Dh); k,v (B,Hkv,S,Dh); GQA broadcast."""
    b, h, s, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    kq = jnp.repeat(k, g, axis=1)
    vq = jnp.repeat(v, g, axis=1)
    if scale is None:
        scale = dh ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        logits = jnp.where(ki <= qi, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def rmsnorm_ref(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(ms + eps)) * weight.astype(jnp.float32)).astype(x.dtype)


def swiglu_ref(gate: jnp.ndarray, up: jnp.ndarray):
    gf = gate.astype(jnp.float32)
    return (jax.nn.silu(gf) * up.astype(jnp.float32)).astype(gate.dtype)
