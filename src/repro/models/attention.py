"""GQA attention: training/prefill (chunked or flash), decode (head-TP or
context-parallel), and cross-attention for encoder–decoder models.

Decode modes (DESIGN.md §5):
  tp — KV cache sharded on the kv-head dim when divisible by the model axis,
       replicated otherwise; each device attends over the full sequence.
  cp — context-parallel: KV cache sharded on the *sequence* dim over the
       model axis (shard_map); each device computes a partial softmax over
       its shard and the results psum-combine (distributed flash-decoding).
       This is the long-context path: cache memory and per-token bandwidth
       scale 1/|model| and only O(B*H*hd) bytes cross the ICI per step.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.shardings import (
    constrain, current_ctx, batch_spec, axes_that_divide, res_constrain)
from repro.kernels import ops
from repro.models.layers import apply_rope, dense_init, rope_freqs

__all__ = ["init_attention", "attention_train", "attention_decode",
           "init_kv_cache", "cross_attention", "encode_kv"]

NEG_INF = -1e30


def init_attention(key, cfg, cross: bool = False):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    pre = "cross_" if cross else ""
    p = {
        pre + "wq": dense_init(ks[0], d, h * hd, dt),
        pre + "wk": dense_init(ks[1], d, hkv * hd, dt),
        pre + "wv": dense_init(ks[2], d, hkv * hd, dt),
        pre + "wo": dense_init(ks[3], h * hd, d, dt, scale=(h * hd) ** -0.5),
    }
    if cfg.qk_norm and not cross:
        p["qn"] = jnp.ones((hd,), dt)
        p["kn"] = jnp.ones((hd,), dt)
    return p


def _qk_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _project_qkv(p, x, cfg, positions, pre=""):
    """x (B,S,D) -> q (B,S,H,hd), k,v (B,S,Hkv,hd), roped + qk-normed."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ba = batch_spec(b)
    q = (x @ p[pre + "wq"]).reshape(b, s, h, hd)
    k = (x @ p[pre + "wk"]).reshape(b, s, hkv, hd)
    v = (x @ p[pre + "wv"]).reshape(b, s, hkv, hd)
    q = constrain(q, ba, None, "model", None)
    k = constrain(k, ba, None, "model", None)
    v = constrain(v, ba, None, "model", None)
    if cfg.qk_norm and not pre:
        q = _qk_norm(q, p["qn"], cfg.norm_eps)
        k = _qk_norm(k, p["kn"], cfg.norm_eps)
    if not pre:   # self-attention: RoPE
        cos, sin = rope_freqs(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _gqa_logits(q, k, scale):
    """q (B,c,H,hd), k (B,S,Hkv,hd) -> logits (B,Hkv,g,c,S) in f32."""
    b, c, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, c, hkv, g, hd)
    return jnp.einsum("bchgd,bshd->bhgcs", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def _gqa_out(w, v):
    """w (B,Hkv,g,c,S), v (B,S,Hkv,hd) -> (B,c,H,hd)."""
    b, hkv, g, c, s = w.shape
    out = jnp.einsum("bhgcs,bshd->bchgd", w, v.astype(jnp.float32))
    return out.reshape(b, c, hkv * g, -1)


def _chunked_causal_attention(q, k, v, cfg, q_offset=0):
    """Memory-bounded causal attention: scan over query chunks.

    Peak logits memory is (B, Hkv, g, chunk, S) f32 instead of (.., S, S).
    On TPU, cfg.attn_impl == "flash" routes to the Pallas kernel instead.
    """
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    c = min(cfg.attn_chunk, s)
    if s % c:
        c = s
    n = s // c
    k_pos = jnp.arange(k.shape[1])

    qs = q.reshape(b, n, c, h, hd).swapaxes(0, 1)   # (n, B, c, H, hd)

    def chunk_fwd(i, qc):
        logits = _gqa_logits(qc, k, scale)          # (B,Hkv,g,c,S)
        q_pos = q_offset + i * c + jnp.arange(c)
        mask = k_pos[None, :] <= q_pos[:, None]
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        return _gqa_out(w, v)

    if cfg.remat != "none":
        # flash-style backward: never keep (c, S) softmax weights across
        # chunks — each chunk's backward recomputes its own logits.
        chunk_fwd = jax.checkpoint(chunk_fwd)

    def chunk(carry, inp):
        i, qc = inp
        return carry, chunk_fwd(i, qc)

    _, outs = jax.lax.scan(chunk, 0, (jnp.arange(n), qs),
                           unroll=True if cfg.unroll else 1)
    return outs.swapaxes(0, 1).reshape(b, s, h, hd).astype(q.dtype)


def attention_train(p, x, cfg, positions):
    """Full-sequence causal self-attention (train / prefill).

    Returns (out (B,S,D), kv) — kv is the prefill cache contribution.
    """
    b, s, _ = x.shape
    ba = batch_spec(b)
    q, k, v = _project_qkv(p, x, cfg, positions)
    if cfg.attn_impl == "flash" and ops.on_tpu():
        o = ops.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                v.swapaxes(1, 2), causal=True)
        o = o.swapaxes(1, 2)
    else:
        o = _chunked_causal_attention(q, k, v, cfg)
    o = constrain(o, ba, None, "model", None)
    out = o.reshape(b, s, -1) @ p["wo"]
    return res_constrain(out, ba), (k, v)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, cache_len: int, dtype=None):
    """One layer's KV cache buffers (B, S, Hkv, hd)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (batch, cache_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _update_cache(cache_arr, new, pos):
    """Write new (B,1,Hkv,hd) at per-example positions pos (B,)."""
    def upd1(c, n, p):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (p, 0, 0))
    return jax.vmap(upd1)(cache_arr, new, pos)


def _decode_attend(q, ck, cv, pos, scale):
    """q (B,1,H,hd); ck/cv (B,S,Hkv,hd); mask k_pos <= pos[b]."""
    logits = _gqa_logits(q, ck, scale)                     # (B,Hkv,g,1,S)
    k_pos = jnp.arange(ck.shape[1])
    mask = k_pos[None, :] <= pos[:, None]                  # (B,S)
    logits = jnp.where(mask[:, None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return _gqa_out(w, cv)                                 # (B,1,H,hd) f32


def attention_decode(p, x, cfg, cache, pos, mode: str = "tp"):
    """One-token decode step.  x (B,1,D), pos (B,) current positions.

    Returns (out (B,1,D), updated cache).
    """
    b = x.shape[0]
    ba = batch_spec(b)
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None].astype(jnp.float32))
    scale = cfg.hd ** -0.5
    ctx = current_ctx()
    use_cp = (mode == "cp" and ctx.mesh is not None
              and ctx.axis_size(ctx.model_axis) > 1
              and cache["k"].shape[1] % ctx.axis_size(ctx.model_axis) == 0)
    if use_cp:
        o, cache = _cp_decode(q, k_new, v_new, cache, pos, cfg, scale)
    else:
        ck = _update_cache(cache["k"], k_new, pos)
        cv = _update_cache(cache["v"], v_new, pos)
        cache = {"k": ck, "v": cv}
        o = _decode_attend(q, ck, cv, pos, scale).astype(x.dtype)
    o = constrain(o, ba, None, "model", None)
    out = o.reshape(b, 1, -1) @ p["wo"]
    return res_constrain(out, ba), cache


def _cp_decode(q, k_new, v_new, cache, pos, cfg, scale):
    """Context-parallel decode: cache seq-sharded over the model axis.

    Each shard holds S/m cache slots; the owning shard writes the new KV;
    all shards compute partial (max, sum, weighted-V) statistics over their
    slots and combine with three psums — distributed flash-decoding.
    """
    ctx = current_ctx()
    mesh = ctx.mesh
    ax = ctx.model_axis
    bs = batch_spec(q.shape[0], ctx)   # tuple of axes or None

    def local(q, kn, vn, ck, cv, pos):
        i = jax.lax.axis_index(ax)
        s_loc = ck.shape[1]
        start = i * s_loc
        loc = pos - start
        in_rng = jnp.logical_and(loc >= 0, loc < s_loc)
        loc_c = jnp.clip(loc, 0, s_loc - 1)

        def upd1(c, n, p_, ok):
            upd = jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (p_, 0, 0))
            return jnp.where(ok, upd, c)
        ck = jax.vmap(upd1)(ck, kn, loc_c, in_rng)
        cv = jax.vmap(upd1)(cv, vn, loc_c, in_rng)

        logits = _gqa_logits(q, ck, scale)                 # (B,Hkv,g,1,Sl)
        k_pos = start + jnp.arange(s_loc)
        mask = k_pos[None, :] <= pos[:, None]
        logits = jnp.where(mask[:, None, None, None, :], logits, NEG_INF)
        m_loc = jnp.max(logits, axis=-1)                   # (B,Hkv,g,1)
        m_glob = jax.lax.pmax(m_loc, ax)
        p_ = jnp.exp(logits - m_glob[..., None])
        l_loc = jnp.sum(p_, axis=-1)
        acc_loc = jnp.einsum("bhgcs,bshd->bhgcd", p_, cv.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, ax)
        acc = jax.lax.psum(acc_loc, ax)
        o = acc / jnp.maximum(l_glob, 1e-30)[..., None]    # (B,Hkv,g,1,hd)
        b, hkv, g, c, hd = o.shape
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, c, hkv * g, hd)
        return o, ck, cv

    o, ck, cv = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bs, None, None, None), P(bs, None, None, None),
                  P(bs, None, None, None), P(bs, ax, None, None),
                  P(bs, ax, None, None), P(bs)),
        out_specs=(P(bs, None, None, None), P(bs, ax, None, None),
                   P(bs, ax, None, None)),
    )(q, k_new, v_new, cache["k"], cache["v"], pos)
    return o.astype(q.dtype), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------

def encode_kv(p, enc_out, cfg):
    """Project encoder output once into cross-attention KV (static cache)."""
    b, s, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.hd
    k = (enc_out @ p["cross_wk"]).reshape(b, s, hkv, hd)
    v = (enc_out @ p["cross_wv"]).reshape(b, s, hkv, hd)
    ba = batch_spec(b)
    return {"k": constrain(k, ba, None, "model", None),
            "v": constrain(v, ba, None, "model", None)}


def cross_attention(p, x, cfg, cross_kv, enc_valid_len=None):
    """x (B,S,D) attends over encoder KV (no causal mask)."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ba = batch_spec(b)
    q = (x @ p["cross_wq"]).reshape(b, s, h, hd)
    q = constrain(q, ba, None, "model", None)
    logits = _gqa_logits(q, cross_kv["k"], hd ** -0.5)
    if enc_valid_len is not None:
        k_pos = jnp.arange(cross_kv["k"].shape[1])
        logits = jnp.where((k_pos[None, :] < enc_valid_len[:, None])
                           [:, None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = _gqa_out(w, cross_kv["v"]).astype(x.dtype)
    o = constrain(o, ba, None, "model", None)
    return constrain(o.reshape(b, s, -1) @ p["cross_wo"], ba, None, None)
