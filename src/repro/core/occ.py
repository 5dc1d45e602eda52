"""Generic Optimistic Concurrency Control (OCC) scaffolding — paper §1.1.

The OCC pattern: partition data over P processors; each epoch every
processor optimistically processes its block of b points against the
replicated global state C^{t-1}; operations that may violate serial
invariants (new cluster / feature proposals) are *serially validated*;
accepted state changes are replicated before the next epoch.

TPU adaptation (see DESIGN.md §2): proposals within an epoch are produced by
one batched, MXU-tiled computation over the Pb points (the per-point
decisions depend only on C^{t-1}, so vectorization preserves the serial
order of Thm 3.1); validation is a deterministic `lax.scan` in global index
order, executed replicated on every device (SPMD re-execution of the
"master") or gathered to a single device (classic mode).

The precomputed fast path is the ONLY engine validator (DESIGN.md §9/§11):
`precomputed_gather_validate` batches every D-dimensional quantity into one
MXU precompute (`ValidatePre`) and then runs a D-free serializing
resolution — for DP-means/OFL the log-depth fixed point
(`logdepth_validate`, with the serial payload scan `precomputed_validate`
as its pool-overflow fallback); for BP-means the Gram-carry scan
(`precomputed_validate_gram`).  The legacy
per-step D-dimensional recompute survives only as a reference
implementation in `core/_reference.py` (tests + benchmark baselines);
`serial_validate` below remains as the vehicle for the paper's *serial*
algorithms (Alg. 1/7 and Meyerson's OFL), which are definitions, not an
engine path.

The global center/feature set C grows over time; JAX needs static shapes, so
C lives in a fixed-capacity masked buffer (`CenterPool`). Overflow is
detected and surfaced — it is the analogue of the paper's master running out
of memory.
"""
from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objective import sq_dists
from repro.kernels import ops as _kops
from repro.kernels.ref import MATMUL_PRECISION

__all__ = [
    "CenterPool", "make_pool", "pool_append_serial", "block_epochs",
    "next_pow2", "serial_validate", "nearest_center",
    "nearest_center_with_new", "OCCStats", "ValidatePre",
    "precomputed_validate", "precomputed_validate_gram",
    "logdepth_validate", "precomputed_gather_validate",
]


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1).  The shared bucketing
    primitive: the engine's adaptive validator cap and the serving plane's
    capacity/request buckets (serving/snapshot.next_bucket) both quantize
    through this, so jit caches key on a handful of shapes."""
    p = 1
    while p < n:
        p <<= 1
    return p


class CenterPool(NamedTuple):
    """Fixed-capacity masked buffer holding the global state C."""
    centers: jnp.ndarray   # (K_max, D)
    mask: jnp.ndarray      # (K_max,) bool — slot holds a validated center
    count: jnp.ndarray     # () int32 — number of valid slots (== mask.sum())
    overflow: jnp.ndarray  # () bool — a validated accept did not fit


class OCCStats(NamedTuple):
    """Per-epoch bookkeeping used by the Fig-3 / Thm-3.3 experiments.

    `cap` records the bounded-master compaction width each epoch actually
    ran with (the epoch width when the master was unbounded) — the
    observability surface for the Thm-3.3 adaptive cap (DESIGN.md §11):
    `proposed[t] > cap[t]` is exactly the sent-overflow condition the
    engine's adaptive mode retries on.  Serial algorithms construct their
    placeholder stats with `cap=None`.
    """
    proposed: jnp.ndarray  # (T,) number of points sent to the validator
    accepted: jnp.ndarray  # (T,) number of proposals accepted as new centers
    cap: jnp.ndarray | None = None  # (T,) int32 validator cap per epoch


def make_pool(k_max: int, dim: int, dtype=jnp.float32) -> CenterPool:
    return CenterPool(
        centers=jnp.zeros((k_max, dim), dtype),
        mask=jnp.zeros((k_max,), bool),
        count=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), bool),
    )


def nearest_center(pool: CenterPool, x: jnp.ndarray,
                   backend: str = "auto") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Min squared distance and argmin over valid centers.

    x: (..., D).  Returns (d2min (...,), idx (...,)).  Empty pool -> +inf / -1.

    Routed through the `kernels/ops.assign` backend dispatch (DESIGN.md §9):
    MXU-tiled Pallas on TPU with the work restricted to a count-rounded
    active prefix of the pool, jnp reference elsewhere.  Sub-tile batches
    (single-point serial-scan steps) stay on the jnp path even on TPU —
    a per-step pallas_call on an 8-row-padded point is pure overhead, and
    keeping the serial references on one primitive preserves their
    bit-exactness against the validator's jnp-computed distances.
    """
    xf = x.reshape(-1, x.shape[-1])
    if backend == "auto" and xf.shape[0] < 8:
        backend = "ref"
    d2min, idx = _kops.assign(xf, pool.centers, pool.mask,
                              count=pool.count, backend=backend)
    batch_shape = x.shape[:-1]
    return d2min.reshape(batch_shape), idx.reshape(batch_shape)


def nearest_center_with_new(pool: CenterPool, x: jnp.ndarray,
                            d2_start: jnp.ndarray, idx_start: jnp.ndarray,
                            count0: jnp.ndarray):
    """`nearest_center` over C^{t-1} ∪ this epoch's accepts, given the
    distance to C^{t-1} already computed in the propose phase.

    Only slots >= count0 (the epoch's new centers) are measured fresh; the
    epoch-start part reuses (d2_start, idx_start) threaded through `aux`.
    On a distance tie the new slot loses: its index is always higher, and a
    full argmin picks the lowest index.  x: (D,) — one validator step.
    """
    k_max = pool.centers.shape[0]
    new_mask = jnp.logical_and(pool.mask, jnp.arange(k_max) >= count0)
    d2 = sq_dists(x[None, :], pool.centers)[0]
    d2 = jnp.where(new_mask, d2, jnp.inf)
    best_new = jnp.min(d2)
    use_new = best_new < d2_start
    idx = jnp.where(use_new, jnp.argmin(d2), idx_start)
    return jnp.minimum(d2_start, best_new), idx


def pool_append_serial(pool: CenterPool, x: jnp.ndarray, do: jnp.ndarray) -> tuple[CenterPool, jnp.ndarray]:
    """Append x at slot `count` if `do` (traced bool). Returns (pool, slot).

    slot is the written index, or -1 when not written / overflowed.
    """
    k_max = pool.centers.shape[0]
    fits = pool.count < k_max
    write = jnp.logical_and(do, fits)
    slot = jnp.where(write, pool.count, -1)
    idx = jnp.clip(pool.count, 0, k_max - 1)
    centers = jnp.where(
        write,
        jax.lax.dynamic_update_slice(pool.centers, x[None, :].astype(pool.centers.dtype), (idx, 0)),
        pool.centers,
    )
    mask = jnp.where(write, pool.mask.at[idx].set(True), pool.mask)
    count = pool.count + write.astype(jnp.int32)
    overflow = jnp.logical_or(pool.overflow, jnp.logical_and(do, ~fits))
    return CenterPool(centers, mask, count, overflow), slot


def block_epochs(n: int, pb: int) -> int:
    """Number of bulk-synchronous epochs for n points with Pb points/epoch."""
    return max(1, math.ceil(n / pb))


def serial_validate(
    pool: CenterPool,
    send: jnp.ndarray,              # (B,) bool — proposal flags in index order
    payload: jnp.ndarray,           # (B, D) — proposed center / feature vectors
    accept_fn: Callable[[CenterPool, jnp.ndarray, Any], tuple[jnp.ndarray, Any]],
    aux: Any = None,                # per-proposal auxiliary pytree (leading dim B)
) -> tuple[CenterPool, jnp.ndarray, Any]:
    """The serializing validator: a deterministic scan in global index order.

    `accept_fn(pool, x_j, aux_j) -> (accept: bool0-d, append_vec, out_j)`
    decides, given the state *including previously accepted proposals of this
    epoch*, whether proposal j becomes a new center, and what vector to
    append (DP/OFL append x_j itself; BP-means appends the residual, Alg. 8).
    Rejected proposals get their reference resolved by the caller via
    `out_j` (e.g. nearest-center index).

    Returns (pool', slot (B,) int32 — accepted slot or -1, outs).
    This is Alg. 2 (DPValidate) / Alg. 5 (OFLValidate) / Alg. 8 (BPValidate)
    generically; identical on every device, hence safe to run replicated.
    """
    if aux is None:
        aux = jnp.zeros((send.shape[0],), jnp.int32)

    def step(carry, inp):
        pool = carry
        send_j, x_j, aux_j = inp
        accept, append_vec, out_j = accept_fn(pool, x_j, aux_j)
        accept = jnp.logical_and(accept, send_j)
        pool, slot = pool_append_serial(pool, append_vec, accept)
        return pool, (slot, out_j)

    pool, (slots, outs) = jax.lax.scan(step, pool, (send, payload, aux))
    return pool, slots, outs


def effective_cap(cap: int | None, b: int) -> int:
    """The bounded master's actual compaction width for a width-b epoch —
    THE single definition: `precomputed_gather_validate` compacts to it and
    the engine records it in `OCCStats.cap`, so the adaptive overflow check
    (`proposed > cap`) is exact by construction, not by parallel copies."""
    return b if cap is None or cap >= b else cap


def _compact_sent(send: jnp.ndarray, cap: int):
    """Bounded-master compaction: stable indices of the first `cap` sent
    proposals (ascending global order) + the sent_overflow flag.  Shared by
    both validator implementations so their windows are identical."""
    b = send.shape[0]
    n_sent = jnp.sum(send.astype(jnp.int32))
    sent_overflow = n_sent > cap if cap < b else jnp.zeros((), bool)
    order = jnp.argsort(jnp.where(send, jnp.arange(b), b), stable=True)[:cap]
    return order, sent_overflow


def _scatter_back(order: jnp.ndarray, b: int, slots_c: jnp.ndarray, outs_c):
    """Scatter compacted validator verdicts back to the full index space."""
    slots = jnp.full((b,), -1, jnp.int32).at[order].set(slots_c, mode="drop")
    outs = jax.tree.map(
        lambda o: jnp.zeros((b,) + o.shape[1:], o.dtype).at[order].set(o, mode="drop"),
        outs_c,
    )
    return slots, outs


# ---------------------------------------------------------------------------
# Precomputed (D-free) validation — DESIGN.md §9/§11
# ---------------------------------------------------------------------------

class ValidatePre(NamedTuple):
    """Everything D-dimensional the fast validator needs, batched on the MXU.

    Payload-append transactions (DP-means, OFL — the accepted append vector
    IS the payload): a new center can only come from the sent set, so every
    distance the serial scan will ever consult is either payload→C^{t-1}
    (computed once in propose and threaded through `aux`) or
    payload→payload (`pair_d2`); `gram` stays None.

    Gram-append transactions (BP-means — the accepted append vector is the
    validator-refit *residual*): every vector the refit can ever touch is a
    signed combination of sent payloads, so all refit dot products reduce
    to the payload Gram matrix `gram[i, j] = r_i · r_j` and validation
    becomes pure coefficient algebra (`precomputed_validate_gram`);
    d2_start / idx_start / pair_d2 stay None.

    d2_start:  (cap,)  min squared distance to the epoch-start centers.
    idx_start: (cap,)  int32 — that center's slot, -1 when the pool is empty.
    pair_d2:   (cap, cap)  payload pairwise squared distances; row j is
               consulted against proposals appended before j.
    aux:       per-proposal decision scalars (leading dim cap; e.g. OFL's
               uniforms), or None when the rule needs only d2.
    gram:      (cap, cap)  payload inner products r_i · r_j (BP-means), or
               None for payload-append transactions.
    """
    d2_start: jnp.ndarray | None
    idx_start: jnp.ndarray | None
    pair_d2: jnp.ndarray | None
    aux: Any
    gram: jnp.ndarray | None = None


def precomputed_validate(
    pool: CenterPool,
    send_c: jnp.ndarray,            # (cap,) bool — compacted proposal flags
    pre: ValidatePre,
    decide_fn: Callable[[jnp.ndarray, Any], jnp.ndarray],
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The serializing scan with ZERO D-dimensional work per step.

    Same serial semantics as `serial_validate` (deterministic, compaction
    order == global index order), but each step is O(cap) scalar mask/min/
    compare logic over precomputed distances: the carry is (count, overflow,
    per-proposal slots), never the (K_max, D) center buffer.  The caller
    writes the accepted payloads to the pool in ONE batched scatter
    afterwards — O(cap·D) total instead of O(cap·K_max·D) sequential.

    `decide_fn(d2_cur, aux_j) -> bool` is the transaction's accept rule given
    the min squared distance to the *current* pool (epoch-start ∪ this
    epoch's appends).  Returns (slots_c (cap,) int32 — accepted slot or -1,
    refs_c (cap,) int32 — nearest-center reference for rejected proposals,
    the pool's new count, its new overflow flag).
    """
    cap = send_c.shape[0]
    k_max = pool.centers.shape[0]

    def step(carry, inp):
        count, overflow, slots_c = carry
        j, send_j, d2s_j, idxs_j, pair_j, aux_j = inp
        # Distance to this epoch's previously appended proposals: a masked
        # row of the precomputed pairwise matrix (slots_c >= 0 marks them).
        d2_new = jnp.where(slots_c >= 0, pair_j, jnp.inf)
        best_new = jnp.min(d2_new)
        # Strict <: on a tie the full argmin picks the lower slot, which is
        # always the epoch-start center (new slots sit at >= count0).
        use_new = best_new < d2s_j
        d2_cur = jnp.minimum(d2s_j, best_new)
        ref = jnp.where(use_new, slots_c[jnp.argmin(d2_new)], idxs_j)
        acc = jnp.logical_and(decide_fn(d2_cur, aux_j), send_j)
        fits = count < k_max
        app = jnp.logical_and(acc, fits)
        slot = jnp.where(app, count, -1)
        slots_c = jax.lax.dynamic_update_index_in_dim(slots_c, slot, j, 0)
        count = count + app.astype(jnp.int32)
        overflow = jnp.logical_or(overflow, jnp.logical_and(acc, ~fits))
        return (count, overflow, slots_c), ref

    aux = pre.aux
    if aux is None:
        aux = jnp.zeros((cap,), jnp.int32)
    init = (pool.count, pool.overflow, jnp.full((cap,), -1, jnp.int32))
    (count, overflow, slots_c), refs_c = jax.lax.scan(
        step, init, (jnp.arange(cap), send_c, pre.d2_start, pre.idx_start,
                     pre.pair_d2, aux))
    return slots_c, refs_c, count, overflow


def logdepth_validate(
    pool: CenterPool,
    send_c: jnp.ndarray,
    pre: ValidatePre,
    decide_fn: Callable[[jnp.ndarray, Any], jnp.ndarray],
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`precomputed_validate` with the sequential accept chain replaced by a
    log-depth parallel resolution (DESIGN.md §11) — bit-identical verdicts,
    the same four outputs.

    Key algebra: for a monotone threshold rule, accepting is intersective —
    decide(min(a, b), aux) == decide(a, aux) AND decide(b, aux) holds
    *exactly* in floats (min never rounds; DP's `d2 > λ²` and OFL's
    `u < min(1, d2/λ²)` are both monotone, and x ↦ min(1, x/λ²) commutes
    with min elementwise).  The serial recurrence therefore collapses to

        accept_j = base_j ∧ ∀ accepted i<j : surv[i, j]

    with base = decide(d2_start) ∧ send and surv[i, j] = decide(pair_d2[i,
    j], aux_j) — the lexicographically-first independent set of the `¬surv`
    conflict digraph.  It is resolved as a Kleene fixed point of
    boolean-semiring matvecs: each round accepts every still-alive proposal
    with no alive earlier killer and retires its victims, so the round
    count is the conflict graph's greedy chain depth — O(log cap) in the
    paper's low-conflict regime (Thm 3.3), never more than cap — while
    every round is parallel O(cap²) bit work on the precomputed matrix.
    Slots then come from one int32 prefix sum and refs from one masked
    column-min, both exact.

    Pool-capacity overflow makes acceptance rank-dependent (an accepted
    proposal that does not fit is appended nowhere and kills nobody), so
    that rare epoch falls back to the serial scan under `lax.cond` —
    verdicts stay bit-identical there too.  Neither branch touches the
    (K_max, D) pool: the caller's one batched write follows.
    """
    cap = send_c.shape[0]
    k_max = pool.centers.shape[0]
    count0 = pool.count
    aux = pre.aux
    if aux is None:
        aux = jnp.zeros((cap,), jnp.int32)
    aux_row = jax.tree.map(lambda a: a[None, ...], aux)   # broadcast over i

    base = jnp.logical_and(decide_fn(pre.d2_start, aux), send_c)
    # surv[i, j]: would j still accept with i's payload in the pool?
    surv = decide_fn(pre.pair_d2, aux_row)
    tri = jnp.arange(cap)[:, None] < jnp.arange(cap)[None, :]
    kill = jnp.logical_and(~surv, tri)

    def round_(state):
        alive, accepted = state
        blocked = jnp.any(jnp.logical_and(kill, alive[:, None]), axis=0)
        newly = jnp.logical_and(alive, ~blocked)
        accepted = jnp.logical_or(accepted, newly)
        victims = jnp.any(jnp.logical_and(kill, newly[:, None]), axis=0)
        alive = jnp.logical_and(alive, ~jnp.logical_or(newly, victims))
        return alive, accepted

    _, accepted = jax.lax.while_loop(
        lambda s: jnp.any(s[0]), round_,
        (base, jnp.zeros((cap,), bool)))

    def finish():
        rank = jnp.cumsum(accepted.astype(jnp.int32))
        slots_c = jnp.where(accepted, count0 + rank - 1, -1)
        # refs: min over the FINAL accepted prefix — same value set (and the
        # same lowest-index tie-break) the serial chain of minimums sees.
        d2_new = jnp.where(jnp.logical_and(accepted[:, None], tri),
                           pre.pair_d2, jnp.inf)
        best_new = jnp.min(d2_new, axis=0)
        arg_new = jnp.argmin(d2_new, axis=0)
        use_new = best_new < pre.d2_start
        refs_c = jnp.where(use_new, slots_c[arg_new], pre.idx_start)
        return slots_c, refs_c, count0 + rank[-1], pool.overflow

    n_acc = jnp.sum(accepted.astype(jnp.int32))
    return jax.lax.cond(
        count0 + n_acc > k_max,
        lambda: precomputed_validate(pool, send_c, pre, decide_fn),
        finish)


def precomputed_validate_gram(
    pool: CenterPool,
    send_c: jnp.ndarray,            # (cap,) bool — compacted proposal flags
    payload_c: jnp.ndarray,         # (cap, D) — compacted payload residuals
    pre: ValidatePre,
    decide_fn: Callable[[jnp.ndarray, Any], jnp.ndarray],
) -> tuple[CenterPool, jnp.ndarray, jnp.ndarray]:
    """The BP-means serializing scan with ZERO D-dimensional work per step
    (DESIGN.md §11) — the Gram-carry fast path.

    BPValidate (Alg. 8) re-fits each proposed residual r_j against the
    features accepted *earlier this epoch* and appends what remains.  Every
    such feature is a signed combination of sent payloads (by induction:
    f_m = r_{k_m} - Σ z f_l), so the scan carries each accepted feature's
    coefficient row c_m over payloads and derives every refit dot product
    from the precomputed payload Gram matrix G = R Rᵀ (`pre.gram`):

        r · f_m   = (G a) · c_m      with a the running residual's coeffs,
        ‖f_m‖²    = the residual norm² carried from m's own acceptance,
        ‖r - f‖²  = ‖r‖² - 2 r·f + ‖f‖².

    Each inner refit step is O(cap) vector algebra (one dot, two subtracts)
    and runs only `n_acc` times per proposal (`fori_loop` to the number of
    features accepted so far — sequential work tracks the Thm-3.3 conflict
    rate, not the cap), vs the reference's O(K_max · D) coordinate pass per
    step with a (K_max, D) pool carry.  Accepted residuals are materialised
    afterwards in ONE (cap, cap) @ (cap, D) MXU matmul.

    Returns (pool', slots_c (cap,) int32, z_c (cap, K_max) bool — each
    proposal's fit against this epoch's accepted features, scattered to
    pool slots; epoch-new slots are contiguous from count0 by construction).
    The coefficient algebra is exact in real arithmetic but reassociates
    float sums, so vs the D-dimensional reference the contract is
    bit-identical *decisions* (tests/test_validator_equivalence.py) and
    ulp-level centers.
    """
    cap = send_c.shape[0]
    k_max = pool.centers.shape[0]
    count0 = pool.count
    gram = pre.gram
    aux = pre.aux
    if aux is None:
        aux = jnp.zeros((cap,), jnp.int32)

    def step(carry, inp):
        # The pool count is count0 + nacc invariantly (only this scan
        # appends within the epoch), so nacc is the one counter carried.
        coef, gcoef, fnorm2, nacc, overflow = carry
        j, send_j, g_row, aux_j = inp

        def fit(m, st):
            a, u, rn2, z = st
            c_m = coef[m]
            dot = jnp.dot(u, c_m, precision=MATMUL_PRECISION)
            z_m = 2.0 * dot > fnorm2[m]
            a = jnp.where(z_m, a - c_m, a)
            u = jnp.where(z_m, u - gcoef[m], u)
            rn2 = jnp.where(z_m, rn2 - 2.0 * dot + fnorm2[m], rn2)
            return a, u, rn2, z.at[m].set(z_m)

        a0 = (jnp.arange(cap) == j).astype(gram.dtype)
        a, u, rn2, z_j = jax.lax.fori_loop(
            0, nacc, fit, (a0, g_row, g_row[j], jnp.zeros((cap,), bool)))

        acc = jnp.logical_and(decide_fn(rn2, aux_j), send_j)
        fits = count0 + nacc < k_max
        app = jnp.logical_and(acc, fits)
        slot = jnp.where(app, count0 + nacc, -1)
        # Row writes go to an out-of-range index when not appending, so the
        # scatter drops instead of selecting between two full (cap, cap)
        # buffers — keeps the carry update O(cap) per step, not O(cap²).
        row = jnp.where(app, nacc, cap)
        coef = coef.at[row].set(a, mode="drop")
        gcoef = gcoef.at[row].set(u, mode="drop")  # u == G a: new G-row
        fnorm2 = fnorm2.at[row].set(rn2, mode="drop")
        nacc = nacc + app.astype(jnp.int32)
        overflow = jnp.logical_or(overflow, jnp.logical_and(acc, ~fits))
        return (coef, gcoef, fnorm2, nacc, overflow), (slot, z_j)

    z0 = jnp.zeros((cap, cap), gram.dtype)
    init = (z0, z0, jnp.zeros((cap,), gram.dtype),
            jnp.zeros((), jnp.int32), pool.overflow)
    (coef, _, _, nacc, overflow), (slots_c, z_mat) = jax.lax.scan(
        step, init, (jnp.arange(cap), send_c, gram, aux))

    # Epoch-new features occupy contiguous slots [count0, count0 + nacc):
    # scatter the acceptance-ordered fit bits / residual rows to pool slots.
    with jax.named_scope("occ.commit"):
        new_slots = count0 + jnp.arange(cap)
        z_c = jnp.zeros((cap, k_max), bool).at[:, new_slots].set(
            z_mat, mode="drop")
        # ONE MXU materialisation
        feats = jnp.matmul(coef, payload_c, precision=MATMUL_PRECISION)
        widx = jnp.where(jnp.arange(cap) < nacc, new_slots, k_max)
        centers = pool.centers.at[widx].set(
            feats.astype(pool.centers.dtype), mode="drop")
        mask = pool.mask.at[widx].set(True, mode="drop")
    return CenterPool(centers, mask, count0 + nacc, overflow), slots_c, z_c


def precomputed_gather_validate(
    pool: CenterPool,
    send: jnp.ndarray,
    payload: jnp.ndarray,
    aux: Any,
    precompute_fn: Callable[..., ValidatePre],
    decide_fn: Callable[[jnp.ndarray, Any], jnp.ndarray],
    cap: int | None = None,
    replicate: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
):
    """Bounded-master validation — THE engine validator (DESIGN.md §9/§11).

    Compacts the sent proposals (stable order == global index order), runs
    `precompute_fn(pool, payload_c, aux_c, count0)` ONCE on the MXU, then a
    D-free serializing resolution, then scatters verdicts back to the full
    index space.  `pre.gram` set → the BP-means Gram-carry scan; otherwise
    the payload accept chain, resolved as the log-depth fixed point
    (`logdepth_validate`) and written to the pool in one batched scatter.

    `replicate` (optional) constrains the compacted buffers — inputs AND
    every precomputed (cap, …) ValidatePre leaf — to the master's
    replicated sharding before the scan, so GSPMD gathers once at
    compaction instead of resharding mid-scan, at whatever cap the epoch
    runs with (see shardings.occ_validate_sharding).

    The steps run under the named scopes `occ.compact`, `occ.precompute`,
    `occ.scan` and `occ.commit` (the batched pool write and the
    scatter-back), which tag their ops in a profile.
    """
    b = send.shape[0]
    count0 = pool.count
    cap_c = effective_cap(cap, b)
    with jax.named_scope("occ.compact"):
        order, sent_overflow = _compact_sent(send, cap_c)
        send_c = send[order]
        payload_c = payload[order]
        aux_c = None if aux is None else jax.tree.map(lambda a: a[order],
                                                      aux)
        if replicate is not None:
            send_c, payload_c = replicate(send_c), replicate(payload_c)
            aux_c = None if aux_c is None else jax.tree.map(replicate, aux_c)
    with jax.named_scope("occ.precompute"):
        pre = precompute_fn(pool, payload_c, aux_c, count0)
        if replicate is not None:
            pre = jax.tree.map(replicate, pre)
    if pre.gram is not None:
        with jax.named_scope("occ.scan"):
            pool, slots_c, refs_c = precomputed_validate_gram(
                pool, send_c, payload_c, pre, decide_fn)
    else:
        with jax.named_scope("occ.scan"):
            slots_c, refs_c, count, overflow = logdepth_validate(
                pool, send_c, pre, decide_fn)
        # One batched pool write: appended slots are unique by
        # construction; the rest go out of range and drop.
        with jax.named_scope("occ.commit"):
            widx = jnp.where(slots_c >= 0, slots_c, pool.centers.shape[0])
            pool = CenterPool(
                pool.centers.at[widx].set(
                    payload_c.astype(pool.centers.dtype), mode="drop"),
                pool.mask.at[widx].set(True, mode="drop"), count, overflow)
    with jax.named_scope("occ.commit"):
        slots, outs = _scatter_back(order, b, slots_c, refs_c)
    return pool, slots, outs, sent_overflow
