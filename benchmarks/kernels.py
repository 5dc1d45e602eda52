"""Kernel microbenchmarks: jit'd wrapper timings + interpret-mode parity.

On this CPU container the "ref" backend timings are the meaningful ones
(the Pallas path runs interpreted, i.e. Python-speed — validated for
correctness, not speed).  On TPU the same harness times the real kernels.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _time(fn, *args, iters: int = 20) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def _topk_rows(rng, quick: bool, backend: str):
    """serve_topk at retrieval-serving scale (DESIGN.md §16): flat oracle
    (full (B, K) matrix) vs the streaming path (the Pallas kernel on a TPU;
    off the chip the oracle on the static-count prefix slice) vs
    hierarchical multi-probe over the same buffers.  K sweeps 2^12..2^17;
    counts are ragged (~K/4 active) so the active-prefix machinery
    actually earns its rows."""
    from repro.kernels.topk_stream import topk_tile_loads
    from repro.serving.snapshot import build_hier

    rows = []
    b, d, k = 64, 64, 16
    x = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    for kc in ((4096,) if quick else (4096, 32768, 131072)):
        count = kc // 4 + 37
        c = jnp.asarray(rng.normal(size=(kc, d)).astype(np.float32))
        m = jnp.asarray(np.arange(kc) < count)

        # flat: traced count -> no prefix slicing, full-width matmul + sort
        cnt = jnp.asarray(count, jnp.int32)
        us = _time(lambda: ops.serve_topk(x, c, k, mask=m, count=cnt,
                                          backend="ref"))
        rows.append((f"kern_serve_topk_flat_K{kc}", us,
                     f"count={count};k={k};backend=ref"))

        # streaming: host count -> tile skip (pow2 prefix slice off-TPU)
        us = _time(lambda: ops.serve_topk(x, c, k, mask=m, count=count,
                                          backend=backend))
        loads = topk_tile_loads(count, kc)
        rows.append((f"kern_serve_topk_stream_K{kc}", us,
                     f"count={count};k={k};backend={backend};"
                     f"tile_loads={loads}of{-(-kc // 128)}"))

        # multi-probe: p=4 of the hier layout built from the same prefix
        h = build_hier(jnp.where(m[:, None], c, 0), m, count)
        p = min(4, h.n_cells)
        _, cq = ops.serve_topk(x, h.coarse, p, mask=h.coarse_mask,
                               backend="ref")
        cq_np = np.asarray(cq)
        probed = np.unique(cq_np[cq_np >= 0])
        u = len(probed)
        cells = np.full((min(h.n_cells, max(8, u)),), -1, np.int32)
        cells[:u] = probed
        member = np.zeros((b, len(cells)), bool)
        for ui, pc in enumerate(probed):
            member[:, ui] = (cq_np == pc).any(axis=1)
        cells_j, member_j = jnp.asarray(cells), jnp.asarray(member)
        ucnt = jnp.asarray(u, jnp.int32)
        us = _time(lambda: ops.serve_topk_multiprobe(
            x, h.fine, h.fine_ids, h.fine_mask, cells_j, member_j, k,
            u_count=ucnt, backend=backend))
        rows.append((f"kern_serve_topk_multiprobe_K{kc}", us,
                     f"count={count};k={k};p={p};probed={u}of{h.n_cells};"
                     f"shard_cap={h.shard_cap};backend={backend}"))
    return rows


def run(quiet: bool = False, quick: bool = False):
    rng = np.random.default_rng(0)
    rows = []
    backend = "pallas" if ops.on_tpu() else "ref"

    x = jnp.asarray(rng.normal(size=(4096, 64)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    m = jnp.ones((256,), bool)
    us = _time(lambda: ops.pairwise_argmin(x, c, m, backend=backend))
    flops = 2 * 4096 * 256 * 64
    rows.append(("kern_dpmeans_assign_4096x256x64", us,
                 f"backend={backend};gflops={flops / us / 1e3:.2f}"))

    q = jnp.asarray(rng.normal(size=(1, 8, 1024, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 1024, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 1024, 64)).astype(np.float32))
    us = _time(lambda: ops.flash_attention(q, k, v, backend=backend))
    rows.append(("kern_flash_attention_1x8x1024x64", us, f"backend={backend}"))

    xx = jnp.asarray(rng.normal(size=(8192, 2048)).astype(np.float32))
    w = jnp.ones((2048,), jnp.float32)
    us = _time(lambda: ops.rmsnorm(xx, w, backend=backend))
    gbs = 2 * xx.size * 4 / us / 1e3
    rows.append(("kern_rmsnorm_8192x2048", us, f"backend={backend};gbps={gbs:.1f}"))

    g = jnp.asarray(rng.normal(size=(8192, 2048)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(8192, 2048)).astype(np.float32))
    us = _time(lambda: ops.swiglu(g, u, backend=backend))
    rows.append(("kern_swiglu_8192x2048", us, f"backend={backend}"))

    # interpret-mode parity spot check (the Pallas body itself)
    d2p, _ = ops.pairwise_argmin(x[:64], c[:32], m[:32], backend="pallas")
    d2r, _ = ops.pairwise_argmin(x[:64], c[:32], m[:32], backend="ref")
    ok = bool(jnp.allclose(d2p, d2r, atol=1e-4))
    rows.append(("kern_pallas_interpret_parity", 0.0, f"allclose={ok}"))

    rows += _topk_rows(rng, quick, backend)

    if not quiet:
        for r in rows:
            print(f"{r[0]},{r[1]:.0f},{r[2]}")
    return rows


if __name__ == "__main__":
    run()
