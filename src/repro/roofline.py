"""Roofline analysis from compiled dry-run artifacts (DESIGN.md §8).

Three terms per (arch x shape x mesh) cell, all in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory     = HLO_bytes_per_device / HBM_bw_per_chip
  collective = collective_bytes_per_device / link_bw_per_chip

`compiled.cost_analysis()` reports post-SPMD per-device flops/bytes, so the
per-chip division above is the same as the global/(chips*peak) form.

Collective bytes are not in cost_analysis: we parse the post-partitioning
HLO text, sum the output-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, and multiply ops that live
inside while-loop bodies (scan-over-layers) by the known trip count — XLA
keeps the loop rolled, so the static text contains one copy.  Trip counts
are recovered from the HLO itself (scan induction bound) where possible and
fall back to the config's layer count.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

__all__ = ["HW", "parse_collectives", "roofline_terms", "model_flops"]

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (approx, per chip)

HW = {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW, "ici_bw": ICI_BW}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(.+?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# computation headers: `%name (params...) -> type {` — params may contain
# nested parens (tuple-typed scan carries), hence the greedy middle match
_COMP_HDR_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_BODY_RE = re.compile(r"(?:body|condition)=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def _shape_bytes(text: str) -> int:
    """Sum bytes of all shapes found in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int] = field(default_factory=dict)
    count_by_kind: dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str, loop_multiplier: int = 1) -> CollectiveStats:
    """Sum collective bytes from post-SPMD HLO text.

    XLA keeps lax.scan rolled (one while body in the text), so collectives
    inside computations referenced as while bodies — or reachable from them
    via calls= — are scaled by `loop_multiplier` (the dominant scan's trip
    count: the layer count for our stacks).  Nested scans of different trip
    counts get the same single multiplier (documented approximation; the
    cell JSON stores raw and scaled numbers).
    """
    # Pass 1: collectives + call edges per computation, loop-body names.
    per_comp: dict[str, dict[str, int]] = {}
    per_comp_cnt: dict[str, dict[str, int]] = {}
    calls: dict[str, set] = {}
    body_names: set[str] = set()
    cur = ""
    for line in hlo_text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr:
            cur = hdr.group(1)
            continue
        for name in _BODY_RE.findall(line):
            body_names.add(name)
        for name in _CALLS_RE.findall(line):
            calls.setdefault(cur, set()).add(name)
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        nbytes = _shape_bytes(m.group(1))
        d = per_comp.setdefault(cur, {})
        d[kind] = d.get(kind, 0) + nbytes
        c = per_comp_cnt.setdefault(cur, {})
        c[kind] = c.get(kind, 0) + 1

    # Pass 2: computations transitively reachable from loop bodies.
    in_loop: set[str] = set()
    frontier = set(body_names)
    while frontier:
        nxt = set()
        for name in frontier:
            if name in in_loop:
                continue
            in_loop.add(name)
            nxt |= calls.get(name, set())
        frontier = nxt - in_loop

    stats = CollectiveStats()
    for comp, kinds in per_comp.items():
        if not isinstance(kinds, dict):
            continue
        mult = loop_multiplier if comp in in_loop else 1
        for kind, nbytes in kinds.items():
            stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes * mult
    for comp, kinds in per_comp_cnt.items():
        for kind, n in kinds.items():
            stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + n
    return stats


def parse_collectives_nested(hlo_text: str, loop_trips: list[int]) -> CollectiveStats:
    """Depth-aware variant: `loop_trips[d]` is the trip count of while loops
    at nesting depth d (0 = outermost, e.g. [microbatches, layers]).  A
    collective inside a depth-d body is scaled by prod(loop_trips[:d+1]);
    deeper loops than provided reuse the last trip count once (inner chunk
    scans typically hold no collectives)."""
    per_comp: dict[str, dict[str, int]] = {}
    calls: dict[str, set] = {}
    while_bodies: dict[str, set] = {}   # comp -> bodies of whiles inside it
    cur = ""
    for line in hlo_text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr:
            cur = hdr.group(1)
            continue
        for name in _BODY_RE.findall(line):
            while_bodies.setdefault(cur, set()).add(name)
        for name in _CALLS_RE.findall(line):
            calls.setdefault(cur, set()).add(name)
        m = _COLL_RE.search(line)
        if m:
            d = per_comp.setdefault(cur, {})
            d[m.group(2)] = d.get(m.group(2), 0) + _shape_bytes(m.group(1))

    # nesting depth per computation (ENTRY not in body sets -> depth 0)
    all_bodies = set().union(*while_bodies.values()) if while_bodies else set()
    roots = set(per_comp) | set(calls) | set(while_bodies)
    depth: dict[str, int] = {c: 0 for c in roots - all_bodies}
    frontier = list(depth)
    while frontier:
        c = frontier.pop()
        dc = depth[c]
        for b in while_bodies.get(c, ()):       # entering a while: depth+1
            if depth.get(b, -1) < dc + 1:
                depth[b] = dc + 1
                frontier.append(b)
        for b in calls.get(c, ()):              # fusion call: same depth
            if depth.get(b, -1) < dc:
                depth[b] = dc
                frontier.append(b)

    stats = CollectiveStats()
    for comp, kinds in per_comp.items():
        d = depth.get(comp, 0)
        mult = 1
        for i in range(min(d, len(loop_trips))):
            mult *= loop_trips[i]
        if d > len(loop_trips) and loop_trips:
            mult *= loop_trips[-1]
        for kind, nbytes in kinds.items():
            stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes * mult
            stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> dict:
    compute = flops_per_dev / PEAK_FLOPS
    memory = bytes_per_dev / HBM_BW
    collective = coll_bytes_per_dev / ICI_BW
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dom = max(terms, key=lambda k: terms[k])
    terms["dominant"] = dom
    bound = max(compute, memory, collective)
    terms["roofline_fraction"] = compute / bound if bound > 0 else 0.0
    return terms


def model_flops(arch, shape, n_params: int, n_active: int | None = None) -> float:
    """MODEL_FLOPS: 6*N*D train / 2*N*D forward, N_active for MoE."""
    n = n_active if n_active is not None else n_params
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch   # decode: one token per example


def active_params(arch, n_params: int, model=None) -> int:
    """N_active for MoE archs: expert params scaled by top_k / n_experts."""
    if arch.moe is None:
        return n_params
    e, k = arch.moe.n_experts, arch.moe.top_k
    expert = arch.n_layers * 3 * arch.d_model * arch.d_ff * e
    return int(n_params - expert + expert * (k / e))


# ===========================================================================
# Analytic cost model (per DESIGN.md §8 and EXPERIMENTS.md §Roofline).
#
# XLA's cost_analysis() counts a rolled while-loop body ONCE, so for
# scan-over-layers programs the compiled numbers undercount by ~L.  The
# roofline therefore uses this analytic model — exact matmul FLOP counts per
# block type — validated against cost_analysis() on small *unrolled* configs
# (tests/test_roofline.py).  Collective bytes still come from the HLO parse.
#
# Conventions: matmul(m,n,k) = 2mnk FLOPs; T = tokens processed; causal
# attention scores cost 1/2 of full.  Train multiplier: fwd + 2x bwd + 1x
# remat recompute = 4x fwd (remat="full"), 3x without.
# ===========================================================================

def _attn_fwd_flops(cfg, t: int, s_ctx: int, causal: bool = True) -> float:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    proj = 2.0 * t * d * (2 * h * hd + 2 * hkv * hd)      # q, o, k, v
    sc = 0.5 if causal else 1.0
    scores = 2.0 * t * s_ctx * h * hd * sc * 2            # qk^T + w.v
    return proj + scores


def _mlp_fwd_flops(cfg, t: int) -> float:
    return 6.0 * t * cfg.d_model * cfg.d_ff


def _moe_fwd_flops(cfg, t: int, seq: int) -> float:
    d, f = cfg.d_model, cfg.d_ff
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    router = 2.0 * t * d * e
    experts = 6.0 * t * k * d * f
    cap = min(int(math.ceil(seq * k / e * cfg.moe.capacity_factor)), seq)
    if cfg.moe.impl == "capacity":
        dispatch = 2 * (2.0 * t * e * cap * d)   # dispatch + combine einsums
    elif cfg.moe.impl == "hybrid":
        dispatch = 2.0 * t * e * cap * d         # combine einsum only
    else:
        dispatch = 0.0                           # gather / ragged / dense
    return router + experts + dispatch


def _mamba_fwd_flops(cfg, t: int) -> float:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    n = cfg.ssm_state
    hs = di // cfg.ssm_head_dim
    q = cfg.ssm_chunk
    in_proj = 2.0 * t * d * (2 * di + 2 * n + hs)
    conv = 2.0 * t * di * cfg.conv_width
    intra = 2.0 * t * q * (n + di) * 0.5          # causal-masked chunk matmuls
    inter = 2.0 * t * di * n * 2                  # y_inter + state update
    out = 2.0 * t * di * d
    return in_proj + conv + intra + inter + out


def _mlstm_fwd_flops(cfg, t: int) -> float:
    d, h = cfg.d_model, cfg.n_heads
    hd = d // h
    q = cfg.ssm_chunk
    proj = 2.0 * t * d * (5 * d + 2 * h)          # q,k,v,og,wo + gates
    intra = 6.0 * t * q * d * 0.5                 # g, y_num, n_num (causal)
    inter = 2.0 * t * d * hd * 2                  # C.q + state outer products
    return proj + intra + inter


def _slstm_fwd_flops(cfg, t: int) -> float:
    d, h = cfg.d_model, cfg.n_heads
    hd = d // h
    proj = 2.0 * t * d * (2 * d + 2 * h) + 2.0 * t * d * d
    recur = t * h * (2 * 2 * hd * hd + 8 * hd)    # zg_r/og_r matvecs + gates
    return proj + recur


def _block_fwd_flops(cfg, kind: str, t: int, s_ctx: int, seq: int) -> float:
    if kind in ("attn_mlp", "shared_attn", "enc_attn_mlp"):
        f = _attn_fwd_flops(cfg, t, s_ctx, causal=(kind != "enc_attn_mlp"))
        if cfg.d_ff:
            f += _mlp_fwd_flops(cfg, t)
        return f
    if kind == "attn_moe":
        return _attn_fwd_flops(cfg, t, s_ctx) + _moe_fwd_flops(cfg, t, seq)
    if kind == "dec_attn_mlp":
        f = _attn_fwd_flops(cfg, t, s_ctx)
        # cross attention: proj for q/o on T, kv on T_enc, scores over S_enc
        d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        b = max(t // max(seq, 1), 1)
        t_enc = b * cfg.frontend_len
        f += 2.0 * t * d * 2 * h * hd + 2.0 * t_enc * d * 2 * hkv * hd
        f += 2.0 * t * cfg.frontend_len * h * hd * 2
        f += _mlp_fwd_flops(cfg, t)
        return f
    if kind == "mamba":
        return _mamba_fwd_flops(cfg, t)
    if kind == "mlstm":
        return _mlstm_fwd_flops(cfg, t)
    if kind == "slstm":
        return _slstm_fwd_flops(cfg, t)
    raise ValueError(kind)


def analytic_flops(arch, shape, segments) -> dict:
    """Global forward/step FLOPs for one cell, by component."""
    b, s = shape.global_batch, shape.seq_len
    kind = shape.kind
    if kind in ("train", "prefill"):
        t, s_ctx = b * s, s
    else:
        t, s_ctx = b, s                           # one token, full-cache scores
    out: dict[str, float] = {}
    body = 0.0
    for (k, count, _sh) in segments:
        body += count * _block_fwd_flops(arch, k, t, s_ctx, s)
    out["body_fwd"] = body
    if arch.is_encdec:
        t_enc = (b if kind != "train" and kind != "prefill" else b) * arch.frontend_len
        t_enc = b * arch.frontend_len
        enc = arch.enc_layers * _block_fwd_flops(arch, "enc_attn_mlp",
                                                 t_enc, arch.frontend_len, arch.frontend_len)
        if kind == "decode":
            enc = 0.0                             # encoder ran at prefill
        out["encoder_fwd"] = enc
        body += enc
    head_t = t if kind != "decode" else b
    if kind == "prefill":
        head_t = b                                # only last-position logits
    out["lm_head_fwd"] = 2.0 * head_t * arch.d_model * arch.vocab
    fwd = body + out["lm_head_fwd"]
    out["fwd_total"] = fwd
    if kind == "train":
        mult = 4.0 if arch.remat == "full" else 3.0
        out["train_mult"] = mult
        out["step_total"] = fwd * mult
    else:
        out["step_total"] = fwd
    return out


def analytic_bytes(arch, shape, segments, mesh_shape: dict,
                   n_params: int) -> dict:
    """Per-DEVICE HBM bytes for one step (the memory-roofline numerator).

    Model: TP weight shards are read once per matmul use (attention scores
    stay in VMEM — the Pallas flash path is the TPU target); activations
    count residual-width tensors in/out per block; decode reads its cache
    shard once per token.  Coefficients documented inline; validated for
    order against memory_analysis/cost_analysis in tests.
    """
    chips = math.prod(mesh_shape.values())
    model_ax = mesh_shape.get("model", 1)
    data_ax = chips // model_ax
    b, s = shape.global_batch, shape.seq_len
    dt = 2 if arch.dtype == "bfloat16" else 4
    d = arch.d_model
    kind = shape.kind
    t_dev = (b * s) / data_ax if kind in ("train", "prefill") else b / data_ax

    w_shard = n_params * dt / chips
    w_gathered = n_params * dt / model_ax        # what compute actually reads
    out: dict[str, float] = {}
    if kind == "train":
        # fwd + remat recompute + dgrad + wgrad weight reads; grads f32 RW;
        # AdamW: read+write mu/nu/params (f32-equivalents sharded over chips)
        out["weights"] = 4 * w_gathered
        out["optimizer"] = (n_params * (4 + 4 + 4) * 2 + n_params * 4 * 2) / chips
        act_coeff = 12.0                          # residual-width tensors per block
        n_blocks = sum(c for _, c, _ in segments) + arch.enc_layers
        out["activations"] = act_coeff * n_blocks * t_dev * d * dt * 2
        out["logits"] = 2 * t_dev * (arch.vocab / model_ax) * 4 * 2
    elif kind == "prefill":
        out["weights"] = w_gathered
        act_coeff = 6.0
        n_blocks = sum(c for _, c, _ in segments) + arch.enc_layers
        out["activations"] = act_coeff * n_blocks * t_dev * d * dt
        out["cache_write"] = _cache_bytes(arch, segments, b, s, dt) / chips
        out["logits"] = 2 * (b / data_ax) * (arch.vocab / model_ax) * 4
    else:
        out["weights"] = w_gathered               # every weight read per token
        out["cache_rw"] = _cache_bytes(arch, segments, b, s, dt) / chips
        out["activations"] = 24.0 * sum(c for _, c, _ in segments) * t_dev * d * dt
        out["logits"] = 2 * (b / data_ax) * (arch.vocab / model_ax) * 4
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _cache_bytes(arch, segments, b: int, s: int, dt: int) -> float:
    """Global decode-state bytes across all layers."""
    total = 0.0
    for kind, count, _ in segments:
        if kind in ("attn_mlp", "attn_moe", "shared_attn", "dec_attn_mlp"):
            total += count * 2 * b * s * arch.n_kv_heads * arch.hd * dt
            if kind == "dec_attn_mlp":
                total += count * 2 * b * arch.frontend_len * arch.n_kv_heads * arch.hd * dt
        elif kind == "mamba":
            di = arch.ssm_expand * arch.d_model
            hs = di // arch.ssm_head_dim
            total += count * b * (hs * arch.ssm_head_dim * arch.ssm_state * 4
                                  + (arch.conv_width - 1) * di * dt)
        elif kind == "mlstm":
            hd = arch.d_model // arch.n_heads
            total += count * b * arch.n_heads * (hd * hd + hd) * 4
        elif kind == "slstm":
            hd = arch.d_model // arch.n_heads
            total += count * b * arch.n_heads * (3 * hd + 1) * 4
    return total
