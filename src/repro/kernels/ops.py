"""Public jit'd wrappers for the Pallas kernels.

Each kernel has one implementation, the Pallas one, and one oracle in
`kernels/ref.py`.  On a TPU the kernels run compiled; off the chip they
run in Pallas interpret mode, which the tests hold to the oracle.  The
`backend` knob (one of `BACKENDS`) makes the choice explicit:

  backend="auto"      -> pallas on TPU, ref elsewhere (production default)
  backend="pallas"    -> pallas, interpret=True off-TPU (kernel validation)
  backend="ref"       -> pure-jnp oracle
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.dpmeans_assign import (
    dpmeans_assign as _dpmeans_assign,
    screened_assign as _screened_assign,
    takes_screen as _takes_screen,
)
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.swiglu import swiglu as _swiglu
from repro.kernels.topk_stream import (
    topk_stream as _topk_stream,
    topk_multiprobe_stream as _topk_mp_stream,
)

__all__ = ["assign", "pairwise_argmin", "serve_assign", "serve_topk",
           "serve_topk_multiprobe", "flash_attention", "rmsnorm", "swiglu",
           "on_tpu", "BACKENDS"]

BACKENDS = ("auto", "pallas", "ref")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if backend == "auto":
        return (True, False) if on_tpu() else (False, False)
    if backend == "pallas":
        return True, not on_tpu()
    if backend == "ref":
        return False, False
    raise ValueError(f"unknown backend {backend!r}, expected one of "
                     f"{BACKENDS}")


def assign(x, centers, mask=None, count=None, backend: str = "auto",
           **blocks):
    """Nearest-center assignment — THE OCC propose/validate primitive.

    x (N, D), centers (K, D), mask (K,) bool, count optional traced scalar
    bounding the valid prefix.  Returns (d2min (N,), idx (N,) int32) with
    idx = -1 (and d2min = inf) where no valid center exists; d2min is f32
    on the Pallas path (kernel accumulation dtype) and the input dtype on
    the reference path (preserving nearest_center's precision contract).

    Backend dispatch (DESIGN.md §9): pallas on TPU (MXU-tiled, count-rounded
    active prefix — tiles beyond the pool count are skipped), pallas
    interpret=True off-TPU for kernel validation, jnp reference elsewhere
    (the reference cannot skip work — static shapes — so count folds into
    the mask, which the pool invariant makes a no-op).  On the Pallas path
    a call that `takes_screen` (at least SCREEN_MIN_ROWS rows, more than
    one center tile, default tiles) runs `screened_assign`: the same
    contract, its distances screened in three bf16 MXU passes.
    """
    if mask is None:
        mask = jnp.ones((centers.shape[0],), bool)
    if count is not None:
        mask = jnp.logical_and(mask, jnp.arange(centers.shape[0]) < count)
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        if not blocks and _takes_screen(x.shape[0], x.shape[1],
                                        centers.shape[0]):
            return _screened_assign(x, centers, mask, count=count,
                                    interpret=interp)
        return _dpmeans_assign(x, centers, mask, count=count,
                               interpret=interp, **blocks)
    return _ref.assign_ref(x, centers, mask)


def pairwise_argmin(x, centers, mask=None, backend: str = "auto", **blocks):
    """Raw kernel/oracle pair for parity testing — NOT the production
    primitive (that is `assign`).  Differences are deliberate: no count
    restriction, no -1-on-empty contract, and the reference path computes
    in f32 (the kernel's accumulation dtype) so sweeps compare the Pallas
    body against a like-for-like oracle across input dtypes."""
    if mask is None:
        mask = jnp.ones((centers.shape[0],), bool)
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        return _dpmeans_assign(x, centers, mask, interpret=interp, **blocks)
    return _ref.pairwise_argmin_ref(x, centers, mask)


def serve_assign(x, centers, mask=None, count=None, n_valid=None,
                 backend: str = "auto", **blocks):
    """Bucket-padded assignment — the serving-plane query primitive.

    Same contract as `assign` plus *query*-prefix masking: the service pads
    ragged request batches up to a power-of-two bucket (so jit caches stay
    warm across request sizes) and passes `n_valid`, the count of real
    rows; padding rows come back as (inf, -1) and can never alias a real
    response.  The center-side count prefix (`count`) works exactly as in
    `assign` — one kernel dispatch covers both maskings.
    """
    d2, idx = assign(x, centers, mask, count=count, backend=backend, **blocks)
    if n_valid is not None:
        ok = jnp.arange(x.shape[0]) < n_valid
        d2 = jnp.where(ok, d2, jnp.inf)
        idx = jnp.where(ok, idx, -1)
    return d2, idx


def _next_pow2(n: int) -> int:
    # Local duplicate of core.occ.next_pow2: core.occ imports this module,
    # so importing it back would be a cycle.
    p = 1
    while p < n:
        p <<= 1
    return p


def _static_count(count):
    """The host int behind `count`, or None when it is traced/absent."""
    if count is None or isinstance(count, jax.core.Tracer):
        return None
    try:
        return int(count)
    except Exception:
        return None


def _mask_queries(d2, idx, n_valid):
    if n_valid is None:
        return d2, idx
    ok = (jnp.arange(d2.shape[0]) < n_valid)[:, None]
    return jnp.where(ok, d2, jnp.inf), jnp.where(ok, idx, -1)


def serve_topk(x, centers, k: int, mask=None, count=None, n_valid=None,
               backend: str = "auto", **blocks):
    """k nearest centers per query: (d2 (N, k) ascending, idx (N, k)).

    Serving-plane ranking query with the same bucket/count-prefix masking
    as `serve_assign`; invalid (masked / padded / beyond-count) slots are
    (inf, -1); distance ties break by lower index on EVERY backend.  Full
    backend dispatch (DESIGN.md §16): pallas streams center tiles through
    VMEM carrying k running candidates and skips HBM DMA beyond the active
    prefix (`kernels/topk_stream.py`); "ref" runs the one-matmul +
    `lax.top_k` oracle.  For f32 inputs the two agree bit-exactly — the
    streamed merge is tiling-invariant and the D-contraction is never
    split.
    `topk[..., :1]` equals `serve_assign` bit-exactly on each backend
    (same algebra, same tie-breaking).

    Active-prefix restriction happens at the SOURCE on every backend:
    masked rows are zeroed before the ref matmul / inf-masked per tile in
    the kernel, so NaN/inf-laden payloads in padded slots can never
    surface in — or reorder — the top-k (tests/test_serving.py pins
    this).  When `count` is a HOST int (benchmarks, the retrieval example
    — not the service's traced per-version scalar), the reference path
    off the chip additionally slices the center buffer to the
    pow2-rounded active prefix before any compute, so it pays
    O(pow2(count)) instead of O(K_max) at count << K_max; a prefix slice
    changes no surviving distance bitwise.  k may exceed the (sliced)
    capacity — the overflow columns come back (inf, -1).
    """
    if mask is None:
        mask = jnp.ones((centers.shape[0],), bool)
    static_c = _static_count(count)
    if count is not None:
        mask = jnp.logical_and(mask, jnp.arange(centers.shape[0]) < count)
    if static_c is not None and backend in ("ref", "auto") and not on_tpu():
        kp = min(centers.shape[0], max(_next_pow2(max(static_c, 1)), 8))
        if kp < centers.shape[0]:
            centers, mask = centers[:kp], mask[:kp]
    kk = min(k, centers.shape[0])
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        d2, idx = _topk_stream(x, centers, mask, kk, count=count,
                               interpret=interp, **blocks)
    else:
        d2, idx = _ref.topk_ref(x, centers, kk, mask)
    if kk < k:
        pad = k - kk
        d2 = jnp.concatenate(
            [d2, jnp.full((d2.shape[0], pad), jnp.inf, d2.dtype)], 1)
        idx = jnp.concatenate(
            [idx, jnp.full((idx.shape[0], pad), -1, jnp.int32)], 1)
    return _mask_queries(d2, idx, n_valid)


def serve_topk_multiprobe(x, fine, fine_ids, fine_mask, cells, member,
                          k: int, u_count=None, n_valid=None,
                          backend: str = "auto", **blocks):
    """Top-k over a hierarchical snapshot's probed fine shards.

    x (B, D); fine (n_cells, S, D) + fine_ids/fine_mask (n_cells, S) per
    `serving.snapshot.build_hier`; cells (U,) the microbatch's probed-cell
    union (packed ascending, -1 pad); member (B, U) per-query membership;
    `u_count` the number of real union entries.  Returns (d2 (B, k), idx
    (B, k)) where idx are ORIGINAL flat-snapshot indices — when the union
    covers every active cell and member is all-true, bit-identical to
    `serve_topk` on the flat buffers (the p = all exactness contract,
    DESIGN.md §16).  Pallas streams only the probed shards (the gather
    lives in the BlockSpec index map — unprobed shards never leave HBM);
    ref gathers the union once and runs ONE shared 2-D matmul, the only
    batched-distance formulation XLA reproduces bitwise against the flat
    matmul.
    """
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        d2, idx = _topk_mp_stream(x, fine, fine_ids, fine_mask, cells,
                                  member, k, u_count=u_count,
                                  interpret=interp, **blocks)
    else:
        d2, idx = _ref.topk_multiprobe_ref(x, fine, fine_ids, fine_mask,
                                           cells, member, k)
    return _mask_queries(d2, idx, n_valid)


def flash_attention(q, k, v, causal=True, scale=None, backend: str = "auto",
                    **blocks):
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        return _flash_attention(q, k, v, causal=causal, scale=scale,
                                interpret=interp, **blocks)
    return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)


def rmsnorm(x, weight, eps: float = 1e-6, backend: str = "auto", **blocks):
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        return _rmsnorm(x, weight, eps=eps, interpret=interp, **blocks)
    return _ref.rmsnorm_ref(x, weight, eps=eps)


def swiglu(gate, up, backend: str = "auto", **blocks):
    use_pallas, interp = _resolve(backend)
    if use_pallas:
        return _swiglu(gate, up, interpret=interp, **blocks)
    return _ref.swiglu_ref(gate, up)
