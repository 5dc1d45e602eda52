"""Sharding rules: how every parameter / activation maps onto the mesh.

Axes (DESIGN.md §5):
  pod   — cross-pod data parallelism (gradient all-reduce crosses DCN/ICI-X)
  data  — in-pod data parallelism + ZeRO-3 weight sharding
  model — tensor parallelism (heads / d_ff / experts / vocab), context
          parallelism for long KV caches

All helpers are divisibility-aware: an axis is only used when it evenly
divides the dimension, so e.g. kv_heads=8 on a 16-way model axis falls back
to replication (Megatron-style GQA TP) and global_batch=1 falls back to
context-parallel-only — the decisions the dry-run log records.
"""
from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardCtx", "shard_ctx", "current_ctx", "constrain", "batch_spec",
           "param_specs", "input_shardings", "axes_that_divide",
           "occ_epoch_sharding", "occ_validate_sharding",
           "occ_propose_shard_map", "serve_snapshot_sharding",
           "serve_query_sharding"]


@dataclass
class ShardCtx:
    mesh: Mesh | None = None
    data_axes: tuple[str, ...] = ("pod", "data")   # axes used for batch DP
    model_axis: str = "model"
    # hillclimb levers (see EXPERIMENTS.md §Perf)
    seq_shard_acts: bool = False      # sequence-parallel activations between blocks
    zero3: bool = True                # shard weights over data axes too
    cp_decode_axes: tuple[str, ...] = ("model",)   # KV-cache context-parallel axes
    force_decode_mode: str | None = None           # override tp/cp heuristic

    def axis_size(self, name: str) -> int:
        if self.mesh is None or name not in self.mesh.shape:
            return 1
        return self.mesh.shape[name]

    @property
    def present_data_axes(self) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in self.data_axes if a in self.mesh.shape)


_CTX = ShardCtx()


@contextlib.contextmanager
def shard_ctx(mesh: Mesh | None, **kw):
    """Install a sharding context; model code reads it via current_ctx()."""
    global _CTX
    prev = _CTX
    _CTX = ShardCtx(mesh=mesh, **kw)
    try:
        yield _CTX
    finally:
        _CTX = prev


def current_ctx() -> ShardCtx:
    return _CTX


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= x
    return out


def axes_that_divide(dim: int, axes: tuple[str, ...], ctx: ShardCtx) -> tuple[str, ...]:
    """Largest prefix of `axes` whose total size divides `dim`."""
    out: list[str] = []
    size = 1
    for a in axes:
        s = ctx.axis_size(a)
        if s <= 1:
            continue
        if dim % (size * s) == 0:
            out.append(a)
            size *= s
        else:
            break
    return tuple(out)


def _norm_elem(dim: int, elem, ctx: ShardCtx):
    """Normalize one PartitionSpec element with divisibility fallback."""
    if elem is None:
        return None
    axes = (elem,) if isinstance(elem, str) else tuple(elem)
    ok = axes_that_divide(dim, axes, ctx)
    if not ok:
        return None
    return ok[0] if len(ok) == 1 else ok


def spec_for(shape: tuple[int, ...], elems: tuple, ctx: ShardCtx | None = None) -> P:
    ctx = ctx or _CTX
    assert len(shape) == len(elems), (shape, elems)
    return P(*[_norm_elem(d, e, ctx) for d, e in zip(shape, elems)])


def constrain(x: jax.Array, *elems) -> jax.Array:
    """with_sharding_constraint with divisibility fallback; no-op w/o mesh."""
    ctx = _CTX
    if ctx.mesh is None:
        return x
    spec = spec_for(x.shape, elems, ctx)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def batch_spec(batch: int, ctx: ShardCtx | None = None):
    """Sharding element for the global-batch dim (DP over pod+data)."""
    ctx = ctx or _CTX
    return axes_that_divide(batch, ctx.present_data_axes, ctx) or None


def occ_epoch_sharding(mesh: Mesh, data_axis: str, pb: int,
                       rank: int) -> NamedSharding:
    """Sharding for the OCC engine's stacked (T, pb, ...) epoch inputs
    (DESIGN.md §5): each epoch's pb points are sharded over `data_axis` —
    the paper's P workers — with divisibility fallback to replication.
    The leading epoch dim stays unsharded (it is the scan axis)."""
    ctx = ShardCtx(mesh=mesh, data_axes=(data_axis,))
    elem = _norm_elem(pb, data_axis, ctx)
    return NamedSharding(mesh, P(None, elem, *([None] * (rank - 2))))


def occ_propose_shard_map(propose, mesh: Mesh, data_axis: str, pb: int):
    """The optimistic phase as one program per device (paper Fig. 4): each
    device runs `propose(pool, x_e, state_e)` on its own pb/|data_axis|
    points against the replicated pool, and every output keeps the point
    axis sharded.  Under plain GSPMD the Pallas propose kernel cannot be
    partitioned at all (XLA refuses a Mosaic call it would have to split).
    Returns None when the axis does not divide pb — the epoch then stays
    replicated, as `occ_epoch_sharding` falls back to."""
    if pb % mesh.shape[data_axis]:
        return None
    return jax.shard_map(propose, mesh=mesh,
                         in_specs=(P(), P(data_axis), P(data_axis)),
                         out_specs=P(data_axis), check_vma=False)


def occ_validate_sharding(mesh: Mesh, rank: int) -> NamedSharding:
    """Replicated sharding for the bounded master's compacted (cap, …)
    validator buffers (DESIGN.md §2/§9/§11): validation is SPMD
    re-execution of the master on every device, so the compaction gather
    happens once and the D-free resolution runs on replicated operands —
    no mid-scan resharding.

    Applied to the compacted inputs AND every precomputed `ValidatePre`
    leaf — the (cap, cap) pairwise / Gram matrices included — at whatever
    cap the epoch runs with: replication has no dimension to split, so the
    adaptive cap's shrunken warm/rest-segment buffers (power-of-two
    bucketed, engine §11) all share this one spec and never retrigger
    layout decisions when the window resizes."""
    return NamedSharding(mesh, P(*([None] * rank)))


def serve_snapshot_sharding(mesh: Mesh, rank: int) -> NamedSharding:
    """Replicated placement for published snapshot buffers (DESIGN.md §10):
    the serving data plane is read-only data parallelism — every device
    answers queries against its own full copy of the (capacity, D) model
    version, so query fan-out needs no center-side collectives at all.
    Same placement as the validator's replicated master; delegated so the
    two stay in lockstep by construction."""
    return occ_validate_sharding(mesh, rank)


def serve_query_sharding(mesh: Mesh, data_axis: str, bucket: int,
                         rank: int) -> NamedSharding:
    """Sharding for a bucket-padded query microbatch: rows split over
    `data_axis` (divisibility fallback to replication — buckets are powers
    of two, so any power-of-two axis divides), trailing dims unsharded.
    With the snapshot replicated, each device scores bucket/|data| queries
    and results concatenate with zero cross-device traffic."""
    ctx = ShardCtx(mesh=mesh, data_axes=(data_axis,))
    elem = _norm_elem(bucket, data_axis, ctx)
    return NamedSharding(mesh, P(elem, *([None] * (rank - 1))))


def res_constrain(x: jax.Array, batch_axes) -> jax.Array:
    """Residual-stream constraint between blocks.

    With seq_shard_acts (sequence parallelism), saved activations are stored
    seq-sharded over the model axis — Megatron-SP style: GSPMD inserts the
    all-gather at the next block's projections and the reduce-scatter after
    its output matmul, cutting per-layer saved-residual memory by |model|.
    """
    ctx = _CTX
    seq = ctx.model_axis if ctx.seq_shard_acts else None
    return constrain(x, batch_axes, seq, None)


# ---------------------------------------------------------------------------
# Parameter sharding rules, keyed on parameter path names.
# Convention: path is a "/"-joined key string from the params dict tree.
# Each rule: (regex, per-dim spec template). Templates may use "DATA" (ZeRO
# axes), "MODEL", None. First match wins; unmatched params are replicated.
# ---------------------------------------------------------------------------

_RULES: list[tuple[str, tuple]] = [
    (r"tok_embed$",            ("MODEL", "DATA")),        # (V, D)
    (r"lm_head$",              ("DATA", "MODEL")),        # (D, V)
    (r"(wq|wg|wu|in_w|dt_w|fe_w1|cross_wq)$", ("DATA", "MODEL")),  # (D, out)
    (r"(wk|wv|cross_wk|cross_wv)$", ("DATA", "MODEL")),   # (D, kv_out)
    (r"(wo|wd|out_w|fe_w2|cross_wo)$", ("MODEL", "DATA")),# (in, D)
    (r"router$",               ("DATA", None)),           # (D, E)
    (r"we_(g|u)$",             ("MODEL", "DATA", None)),  # (E, D, F)
    (r"we_d$",                 ("MODEL", None, "DATA")),  # (E, F, D)
    (r"conv_w$",               (None, "MODEL")),          # (width, inner)
    (r"(a_log|d_skip)$",       ("MODEL",)),               # (H_ssm,)
    (r"(qn|kn|norm\w*|.*_norm|gn)$", (None,)),            # norms: replicated
    (r"(ig_w|fg_w|og_w|zg_w)$", ("DATA", "MODEL")),       # xlstm gate projs
    (r"(ig_r|fg_r|og_r|zg_r)$", (None, None)),            # slstm recurrent (small)
]

# Stacked-per-layer params get a leading L dim (replicated) — handled by rank.


def _spec_template_for(path: str) -> tuple | None:
    for pat, tmpl in _RULES:
        if re.search(pat, path):
            return tmpl
    return None


def param_specs(params: Any, ctx: ShardCtx | None = None) -> Any:
    """PartitionSpec pytree matching `params` (arrays or ShapeDtypeStructs)."""
    ctx = ctx or _CTX

    def resolve(path_elems, leaf) -> P:
        path = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path_elems)
        tmpl = _spec_template_for(path)
        shape = leaf.shape
        if tmpl is None:
            return P(*([None] * len(shape)))
        tmpl = tuple(tmpl)
        if len(tmpl) < len(shape):          # stacked layer / segment dims
            tmpl = (None,) * (len(shape) - len(tmpl)) + tmpl
        elif len(tmpl) > len(shape):
            tmpl = tmpl[-len(shape):]
        elems = []
        for d, t in zip(shape, tmpl):
            if t == "DATA":
                elems.append(_norm_elem(d, ctx.present_data_axes, ctx) if ctx.zero3 else None)
            elif t == "MODEL":
                elems.append(_norm_elem(d, ctx.model_axis, ctx))
            else:
                elems.append(_norm_elem(d, t, ctx) if t else None)
        return P(*elems)

    return jax.tree_util.tree_map_with_path(resolve, params)


def input_shardings(tree: Any, ctx: ShardCtx | None = None) -> Any:
    """NamedShardings for a spec pytree (helper for jit in_shardings)."""
    ctx = ctx or _CTX
    assert ctx.mesh is not None
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), tree,
                        is_leaf=lambda s: isinstance(s, P))
