"""Unified OCC engine: one compiled epoch scan for every OCC algorithm.

The paper's observation (and DESIGN.md §2-§3) is that DP-means, OFL, and
BP-means are *one* pattern — optimistic per-point transactions against the
replicated stale state C^{t-1}, plus a serializing validator.  The
`OCCTransaction` protocol captures exactly the algorithm-specific pieces:

  init_pool  — allocate the fixed-capacity global state (may use data stats;
               the engine passes the FIRST EPOCH's points, so batch and
               streaming runs derive identical initializers)
  make_state — per-point auxiliary state for a span of points (e.g. OFL's
               counter-based uniforms, BP-means' previous-pass assignments)
  propose    — the optimistic phase: one batched computation over an epoch's
               points deciding which are sent to the validator
  precompute_accept / accept_pre
             — the validation rule, split into one batched MXU precompute
               (`occ.ValidatePre`) and a D-free scalar decision (§9/§11)
  writeback  — resolve per-point outputs from the validator's verdicts
  refine     — the bulk-synchronous refinement between passes (mean /
               least-squares re-estimation)
  objective  — the algorithm's objective for reporting

`OCCEngine` owns everything the three hand-rolled drivers used to copy:
epoch padding and valid-masking, the serial bootstrap prefix (paper §4.2),
bounded-master validation (`occ.precomputed_gather_validate` — the ONLY
validator; the legacy per-step D-dimensional path lives on solely as the
reference oracle in `core/_reference.py`), mesh sharding of epoch inputs,
and per-epoch statistics.  An entire pass — bootstrap prefix plus all T
bulk-synchronous epochs — runs as a single `jax.lax.scan` inside ONE jit:
the legacy drivers dispatched T compiled epochs from Python and forced a
device→host sync per epoch via `int(n_sent)`; the engine accumulates
`OCCStats` on device and returns them as arrays from the one compiled call
(zero per-epoch host transfers, zero per-epoch dispatch overhead).

Adaptive bounded master (DESIGN.md §11): `validate_cap="adaptive"` sizes the
compaction window from Thm 3.3 — after the bootstrap regime E[#sent per
epoch] ≈ Pb·ε + ΔK, both observable — instead of paying the full (cap, cap)
MXU precompute and O(cap²) scan every epoch.  Caps are power-of-two
bucketed so the jit cache sees a handful of shapes; a pass whose observed
sends exceed its cap (`stats.proposed > stats.cap`) is deterministically
re-dispatched at full width before being committed, so adaptive results are
ALWAYS bit-identical to full-cap results.  The chosen cap is surfaced per
epoch in `OCCStats.cap`.

Transactions are registered as jax pytrees (scalar hyperparameters and rng
keys are leaves; shape-determining fields are static aux data), so the
compiled pass is shared process-wide across engine instances — repeated
calls with the same shapes hit the jit cache exactly like the legacy
module-level epoch jits did.

Streaming: `OCCEngine.partial_fit(batch)` reuses the same transactions and
the same compiled scan for incremental epochs over arriving data — the
online/heavy-traffic serving mode (see examples/streaming_clusters.py).
Batches of ANY length are bit-identical to the one-shot run: the engine
holds back the trailing `n mod pb` points as an explicit partial-epoch
carry so the stream's epoch partition matches the one-shot partition
exactly; `flush()` processes the final short epoch at stream end.  Pool
initialization is deferred to the first committed epoch and computed from
its points, so even data-statistic initializers (BP-means `init_mean`) are
batching-independent.

Train/serve split: the optional `publish=` hook is called with every
committed pass result, so a `serving.SnapshotStore` can freeze immutable
model versions for the read-only serving data plane (DESIGN.md §10) while
the trainer keeps streaming — trainer and service share no mutable state.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.occ import (
    CenterPool, OCCStats, ValidatePre, block_epochs, effective_cap,
    next_pow2, precomputed_gather_validate,
)
from repro.obs import span
from repro.obs.metrics import now as _obs_now

__all__ = ["OCCTransaction", "OCCEngine", "OCCPassResult",
           "resolve_assignments", "accumulate_pass_stats"]


@runtime_checkable
class OCCTransaction(Protocol):
    """What an algorithm must supply to run under the OCC engine.

    Implementations must be registered as jax pytrees (dynamic leaves:
    scalar hyperparameters, rng keys; static aux: anything shape-determining
    such as k_max) so they can flow through the engine's jitted pass.
    """

    def init_pool(self, x: jnp.ndarray) -> CenterPool:
        """Allocate the global state.  The engine calls this with the pass's
        first `pb` points (or everything committed when fewer) — the first
        Pb block, which with a bootstrap prefix spans the prefix plus the
        start of epoch 0 — so data-statistic initializers (BP-means
        `init_mean`) see the same points in one-shot and streaming runs."""
        ...

    def make_state(self, x: jnp.ndarray, offset: int = 0) -> Any:
        """Per-point state pytree (leading dim len(x)) for points starting at
        global index `offset`; () when the transaction is stateless."""
        ...

    def propose(self, pool: CenterPool, x_e: jnp.ndarray, state_e: Any
                ) -> tuple[jnp.ndarray, jnp.ndarray, Any, Any]:
        """Optimistic phase over one epoch's points against C^{t-1}.

        Returns (send (B,) bool, payload (B, D), aux, safe) where `payload`
        is what a sent point proposes (DP/OFL: the point; BP: its residual),
        `aux` is the per-proposal pytree forwarded to the validator (or
        None), and `safe` is the resolved output for points not sent (e.g.
        the nearest-center index, or BP's fitted assignment row).
        """
        ...

    def precompute_accept(self, pool: CenterPool, payload_c: jnp.ndarray,
                          aux_c: Any, count0: jnp.ndarray) -> ValidatePre:
        """Batch-compute every D-dimensional quantity validation can need,
        ONCE on the MXU (REQUIRED — the unified validator contract, §11).

        Payload-append transactions (DP-means, OFL) fill d2_start / idx /
        pair_d2 — reusing the d2/idx the propose phase already found via
        `aux_c` rather than recomputing them; Gram-append transactions
        (BP-means) fill `gram`, the payload inner-product matrix that makes
        the validator refit pure coefficient algebra."""
        ...

    def accept_pre(self, d2_cur: jnp.ndarray, aux_j: Any) -> jnp.ndarray:
        """The D-free accept rule (REQUIRED): given the min squared distance
        to the current pool (payload scan) or the refit residual norm²
        (Gram scan), decide acceptance.  For a payload-append transaction
        it must be an elementwise monotone threshold rule: its accept chain
        is resolved as the log-depth fixed point (§11)."""
        ...

    def accept(self, pool: CenterPool, payload_j: jnp.ndarray, aux_j: Any,
               count0: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, Any]:
        """REFERENCE ONLY — the legacy one-proposal-per-step validation rule
        with full D-dimensional recompute.  The engine never calls it; it
        defines the oracle semantics for `core/_reference.py` and the
        serial algorithms."""
        ...

    def writeback(self, send, slots, outs, safe, valid) -> Any:
        """Combine validator verdicts into the per-point epoch output."""
        ...

    def refine(self, pool: CenterPool, x: jnp.ndarray, assign: Any) -> CenterPool:
        """Bulk-synchronous refinement between passes (identity for OFL)."""
        ...

    def objective(self, x: jnp.ndarray, assign: Any, pool: CenterPool) -> jnp.ndarray:
        ...


class OCCPassResult(NamedTuple):
    """Everything one compiled pass returns — all device arrays."""
    pool: CenterPool
    assign: Any             # (N,) int32 or (N, K_max) bool
    send: jnp.ndarray       # (N,) bool — point hit the validator
    epoch_of: jnp.ndarray   # (N,) int32 — epoch each point was processed in
    stats: OCCStats         # (T,) proposed / accepted / cap, on device


def resolve_assignments(send, slots, outs, safe, valid):
    """The DP/OFL writeback: accepted → new slot, rejected → validator's
    nearest-center ref, not sent → optimistic nearest, padding → -1."""
    z = jnp.where(send, jnp.where(slots >= 0, slots, outs), safe)
    return jnp.where(valid, z, -1).astype(jnp.int32)


def accumulate_pass_stats(stat_parts: list[OCCStats]) -> OCCStats:
    """Concatenate per-pass OCCStats into one globally-epoch-numbered tuple
    (empty input → empty stats).  Shared by the multi-pass wrappers so
    every pass's validator load is recorded, not just pass 1's.  `cap`
    concatenates when every part carries it (engine-produced stats always
    do) and stays None when any part is a serial placeholder."""
    if not stat_parts:
        z = jnp.zeros((0,), jnp.int32)
        return OCCStats(z, z, z)
    caps = [s.cap for s in stat_parts]
    return OCCStats(
        jnp.concatenate([s.proposed for s in stat_parts]),
        jnp.concatenate([s.accepted for s in stat_parts]),
        None if any(c is None for c in caps) else jnp.concatenate(caps))


# Trace counter: incremented only when the pass is (re)compiled.  Lets tests
# assert the epoch loop lives inside a single compilation unit.
_PASS_TRACES = 0

# Adaptive-cap policy constants (DESIGN.md §11): smallest cap ever chosen,
# safety margin on the Thm-3.3 estimate, and the decay floor that keeps one
# quiet pass from collapsing the estimate (a retry costs a full re-dispatch).
ADAPTIVE_CAP_MIN = 8
ADAPTIVE_CAP_MARGIN = 2


def _finish_epoch(txn, pool, send, payload, aux, safe, valid_e, validate_cap,
                  replicate=None):
    """Serialize one epoch's proposals: the master half of an OCC epoch.

    Everything after `propose` — valid-masking, the one true precomputed
    validator, writeback, overflow fold, epoch stats.  Split out of
    `_epoch_body` so the proposal block can come from ANYWHERE (the fused
    scan below, or worker processes streaming proposals over sockets in
    `launch/occ_cluster.py`) while validation stays one code path.

    Its ops carry the named scopes `occ.compact` (valid-masking here, then
    the validator's compaction), `occ.precompute`, `occ.scan` and
    `occ.commit` (the pool write and scatter-back, writeback, overflow fold
    and epoch stats), so a profile splits the master's device time."""
    b = valid_e.shape[0]
    with jax.named_scope("occ.compact"):
        send = jnp.logical_and(send, valid_e)
    pool, slots, outs, sent_ovf = precomputed_gather_validate(
        pool, send, payload, aux, txn.precompute_accept, txn.accept_pre,
        cap=validate_cap, replicate=replicate)
    with jax.named_scope("occ.commit"):
        assign_e = txn.writeback(send, slots, outs, safe, valid_e)
        pool = pool._replace(overflow=jnp.logical_or(pool.overflow, sent_ovf))
        n_sent = jnp.sum(send.astype(jnp.int32))
        n_acc = jnp.sum((slots >= 0).astype(jnp.int32))
        cap = jnp.asarray(effective_cap(validate_cap, b), jnp.int32)
    return pool, (assign_e, send, n_sent, n_acc, cap)


def _epoch_body(txn, pool, x_e, valid_e, state_e, validate_cap,
                replicate=None, propose=None):
    """One bulk-synchronous OCC epoch (any width, incl. the width-1 epochs
    of the serial bootstrap prefix) — always on the precomputed validator.
    `propose` replaces `txn.propose` (the mesh path's per-shard propose)."""
    propose = txn.propose if propose is None else propose
    with jax.named_scope("occ.propose"):
        send, payload, aux, safe = propose(pool, x_e, state_e)
    return _finish_epoch(txn, pool, send, payload, aux, safe, valid_e,
                         validate_cap, replicate)


def _engine_pass(txn, pool, x, state, *, pb, cap_warm, cap_rest, n_warm,
                 n_bootstrap, mesh, data_axis):
    """The whole pass: bootstrap prefix + T epochs, one `lax.scan` each,
    inside one jit.  All sizes static; no host round-trips.

    The main epochs split into up to two statically-shaped segments: the
    first `n_warm` run at `cap_warm` (the bootstrap-regime width — epoch 1
    of a cold pool sends everything, Thm 3.3's burn-in) and the rest at
    `cap_rest` (the adaptive Thm-3.3 bound).  Non-adaptive runs pass
    cap_warm == cap_rest and get the single-segment scan unchanged.

    Named scopes tag every op of the compiled pass for the profiler (op
    metadata only: the program is the same without them): `occ.pass` holds
    the input stack/pad, the epoch loops and the output unstack, and
    inside it each epoch's `occ.propose`, `occ.compact`, `occ.precompute`,
    `occ.scan` and `occ.commit` (see `_finish_epoch`).
    """
    global _PASS_TRACES
    _PASS_TRACES += 1
    with jax.named_scope("occ.pass"):
        n, d = x.shape
        nb = n_bootstrap

        replicate = propose = None
        if mesh is not None:
            # The validator is the replicated master: pin its compacted
            # (cap, …) buffers to the replicated spec so GSPMD gathers once
            # at compaction instead of resharding mid-scan
            # (shardings.occ_validate_sharding).
            from repro.distributed.shardings import (
                occ_propose_shard_map, occ_validate_sharding,
            )
            replicate = lambda a: jax.lax.with_sharding_constraint(
                a, occ_validate_sharding(mesh, a.ndim))
            propose = occ_propose_shard_map(txn.propose, mesh, data_axis, pb)

        def epoch_at(cap, propose=None):
            def epoch(pool, inp):
                return _epoch_body(txn, pool, *inp, cap, replicate, propose)
            return epoch

        # Serial bootstrap prefix (paper §4.2): width-1 epochs are exactly
        # the serial algorithm — each point proposes against the fully
        # up-to-date pool, so this reproduces serial_*_pass on x[:nb].
        assign_b = None
        if nb:
            xb = x[:nb][:, None, :]
            vb = jnp.ones((nb, 1), bool)
            sb = jax.tree.map(lambda s: s[:nb][:, None], state)
            pool, (ab, _, _, _, _) = jax.lax.scan(epoch_at(cap_warm), pool,
                                                  (xb, vb, sb))
            assign_b = jax.tree.map(
                lambda a: a.reshape((nb,) + a.shape[2:]), ab)

        # Main epochs: pad to T*pb, reshape to (T, pb, ...), scan per
        # segment.
        n_rest = n - nb
        t_epochs = block_epochs(n_rest, pb)
        pad = t_epochs * pb - n_rest

        def stack(a):
            flat = jnp.concatenate(
                [a[nb:], jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
            return flat.reshape((t_epochs, pb) + a.shape[1:])

        xs = stack(x)
        valid = stack(jnp.ones((n,), bool))
        ss = jax.tree.map(stack, state)
        if mesh is not None:
            # Shard each epoch's points over the data axis: the optimistic
            # phase parallelizes under GSPMD, the validation scan runs
            # replicated (SPMD re-execution of the master).  See
            # shardings.occ_epoch_spec.
            from repro.distributed.shardings import occ_epoch_sharding
            put = lambda a: jax.lax.with_sharding_constraint(
                a, occ_epoch_sharding(mesh, data_axis, pb, a.ndim))
            xs, valid = put(xs), put(valid)
            ss = jax.tree.map(put, ss)

        t_warm = min(n_warm, t_epochs) if cap_warm != cap_rest else 0
        seg_parts = []
        for cap, lo, hi in ((cap_warm, 0, t_warm),
                            (cap_rest, t_warm, t_epochs)):
            if hi <= lo:
                continue
            cut = lambda a: a[lo:hi]
            pool, part = jax.lax.scan(
                epoch_at(cap, propose), pool,
                (cut(xs), cut(valid), jax.tree.map(cut, ss)))
            seg_parts.append(part)
        am, sm, n_sent, n_acc, caps = jax.tree.map(
            lambda *p: jnp.concatenate(p, 0), *seg_parts)

        unstack = lambda a: a.reshape(
            (t_epochs * pb,) + a.shape[2:])[:n_rest]
        assign = jax.tree.map(unstack, am)
        send = unstack(sm)
        if nb:
            assign = jax.tree.map(lambda b, m: jnp.concatenate([b, m], 0),
                                  assign_b, assign)
            # Bootstrapped points are processed by the master by construction.
            send = jnp.concatenate([jnp.ones((nb,), bool), send], 0)
        epoch_of = jnp.concatenate([
            jnp.zeros((nb,), jnp.int32),
            jnp.repeat(jnp.arange(t_epochs, dtype=jnp.int32), pb)[:n_rest]])
        return OCCPassResult(
            pool, assign, send, epoch_of,
            OCCStats(proposed=n_sent, accepted=n_acc, cap=caps))


_engine_pass_jit = jax.jit(
    _engine_pass,
    static_argnames=("pb", "cap_warm", "cap_rest", "n_warm", "n_bootstrap",
                     "mesh", "data_axis"))


# Per-epoch jits for the host-driven proposal-source path
# (`OCCEngine.run_from_proposals`).  Key bit-identity fact the multi-process
# cluster rests on: a jitted propose at shard shape equals the matching
# slice of the jitted full-epoch propose, and this per-epoch finish equals
# the fused scan's epoch body — so a pass assembled from worker proposal
# blocks reproduces the single-jit `run()` bitwise (tests/test_occ_cluster).
_propose_epoch_jit = jax.jit(
    lambda txn, pool, x_e, state_e: txn.propose(pool, x_e, state_e))

_finish_epoch_jit = jax.jit(
    lambda txn, pool, send, payload, aux, safe, valid_e, validate_cap:
    _finish_epoch(txn, pool, send, payload, aux, safe, valid_e, validate_cap),
    static_argnames=("validate_cap",))


@jax.jit
def _join_state(carried: jnp.ndarray, state: jnp.ndarray) -> jnp.ndarray:
    """One leaf of the partial-epoch carry's state ahead of the batch's, in
    one compiled call under the named scope `occ.state` (a stateless
    transaction has no leaf, so nothing runs)."""
    with jax.named_scope("occ.state"):
        return jnp.concatenate([carried, state], 0)


class OCCEngine:
    """Driver for OCC transactions: batch passes and streaming epochs.

    Args:
      transaction: an `OCCTransaction` (pytree-registered).
      pb: points per epoch (the paper's P*b product — only the product
        matters algorithmically; `mesh` supplies the physical P).
      validate_cap: bounded-master compaction (occ.precomputed_gather_
        validate).  An int fixes the window; None leaves the master
        unbounded; "adaptive" sizes it per pass from the Thm-3.3 bound
        (observed Pb·ε + K growth, ×2 margin, power-of-two bucketed) with a
        full-width first epoch on cold pools and a deterministic full-width
        retry whenever a pass overflows its window — adaptive results are
        bit-identical to full-cap results by construction.  Overflow of an
        int cap is surfaced on `pool.overflow`.
      mesh / data_axis: optional device mesh; each epoch's points are
        sharded over `data_axis` while the validation scan is replicated.
      publish: optional hook `publish(result, n_seen=..., epochs=...,
        cap_est=...)` called after every committed pass (run / partial_fit
        / flush) — the train→serve publication point
        (`SnapshotStore.publish_pass`).  `cap_est` is the adaptive-cap
        estimator at publish time (None when not adaptive), persisted into
        snapshots so `restore()` resumes with a warm cap.
    """

    def __init__(self, transaction: OCCTransaction, pb: int,
                 validate_cap: int | None | str = None,
                 mesh: jax.sharding.Mesh | None = None,
                 data_axis: str = "data",
                 publish: Callable[..., Any] | None = None,
                 obs: Any = None):
        self.txn = transaction
        # Optional telemetry (`repro.obs.Obs`).  None ⇒ ZERO instrumentation
        # cost: no clock reads, no device syncs beyond the caller's own —
        # the occ_engine overhead benchmark A/Bs exactly this switch.
        self.obs = obs
        self.pb = int(pb)
        if isinstance(validate_cap, str) and validate_cap != "adaptive":
            raise ValueError(f"unknown validate_cap {validate_cap!r}")
        self.adaptive = validate_cap == "adaptive"
        self.validate_cap = None if self.adaptive else validate_cap
        self.mesh = mesh
        self.data_axis = data_axis
        self.publish = publish
        self.n_dispatches = 0       # compiled-pass invocations (1 per pass)
        # adaptive-cap observability
        self._cap_est: int | None = None    # None → full width
        self.cap_history: list[int | None] = []   # cap chosen per pass
        self.n_cap_retries = 0
        # streaming state
        self._pool: CenterPool | None = None
        self._n_seen = 0
        self._stat_chunks: list[OCCStats] = []
        self._epoch_base = 0        # global epochs committed so far
        self._carry_x: jnp.ndarray | None = None   # trailing partial epoch
        self._carry_state: Any = None
        self._empty_templates: dict[Any, OCCPassResult] = {}

    # ---------------------------------------------------------- adaptive cap
    def _plan_caps(self, cold: bool) -> tuple[int | None, int | None, int]:
        """(cap_warm, cap_rest, n_warm) for the next dispatched pass."""
        if not self.adaptive:
            return self.validate_cap, self.validate_cap, 0
        rest = self._cap_est
        if rest is None or rest >= self.pb:
            return None, None, 0
        # Cold pool → the first main epoch sends ~everything (Thm 3.3
        # burn-in): keep it full-width, shrink from epoch 2 on.
        return (None, rest, 1) if cold else (rest, rest, 0)

    def _observe_stats(self, stats: OCCStats, cold: bool) -> None:
        """Fold a committed pass's observed load (host arrays) into the
        Thm-3.3 estimate: cap ≈ pow2(2 · (Pb·ε̂ + ΔK̂)) with ε̂, ΔK̂ the
        post-burn-in per-epoch sent rate / pool growth."""
        if not self.adaptive:
            return
        sent, acc = stats.proposed, stats.accepted
        if cold:                       # drop the burn-in epoch's full flood
            sent, acc = sent[1:], acc[1:]
        if sent.size == 0:
            return
        bound = ADAPTIVE_CAP_MARGIN * (int(sent.max()) + int(acc.max()))
        est = next_pow2(max(ADAPTIVE_CAP_MIN, bound))
        if self._cap_est is not None:      # decay floor: halve at most
            est = max(est, self._cap_est // 2)
        self._cap_est = None if est >= self.pb else est

    def _export_pass(self, stats: OCCStats, t0: float, width: str) -> None:
        """Post-pass telemetry export (obs is set): fold the pass's
        `OCCStats`, already read to the host, into the registry and the
        trace WITHOUT adding dispatches — the fused pass stays ONE compiled
        call.  `engine_pass_s` times the pass from dispatch to its stats
        read, labelled by validator `width`; `engine.pass` is the same
        interval in the trace.  The fused scan has no per-epoch host spans:
        per-epoch device time comes from the `occ.*` named scopes in a
        profiler trace."""
        m = self.obs.metrics
        prop, acc, cap = stats.proposed, stats.accepted, stats.cap
        t1 = _obs_now()
        n_epochs = int(prop.shape[0])
        n_prop, n_acc = int(prop.sum()), int(acc.sum())
        m.counter("engine_passes").inc()
        m.counter("engine_epochs").inc(n_epochs)
        m.counter("engine_proposed").inc(n_prop)
        m.counter("engine_accepted").inc(n_acc)
        m.counter("engine_rejected").inc(n_prop - n_acc)
        if n_prop:
            # Thm 3.3 conflict rate ε: rejected fraction of proposals.
            m.gauge("engine_conflict_rate").set((n_prop - n_acc) / n_prop)
        if n_epochs:
            m.gauge("engine_cap").set(int(cap[-1]))
        m.histogram("engine_pass_s", width=width).observe(t1 - t0)
        if self.obs.tracer is not None:
            self.obs.tracer.complete(
                "engine.pass", t0 * 1e6, (t1 - t0) * 1e6, cat="engine",
                args=dict(epochs=n_epochs, proposed=n_prop, accepted=n_acc,
                          dispatches=self.n_dispatches, width=width))

    def _launch(self, pool, x, state, cap_warm, cap_rest, n_warm,
                n_bootstrap, mesh) -> OCCPassResult:
        res = _engine_pass_jit(
            self.txn, pool, x, state, pb=self.pb, cap_warm=cap_warm,
            cap_rest=cap_rest, n_warm=n_warm, n_bootstrap=n_bootstrap,
            mesh=mesh, data_axis=self.data_axis)
        self.n_dispatches += 1
        return res

    def _dispatch(self, pool, x, state, *, n_bootstrap: int, cold: bool,
                  mesh) -> OCCPassResult:
        """One compiled pass, with the adaptive overflow retry: a pass whose
        observed sends exceed its window is re-dispatched at full width
        (deterministic — same inputs), so committed adaptive results are
        always bit-identical to full-cap results.

        The stats are read to the host only where they are used (adaptive
        caps, telemetry); that read is where the host waits on the device.
        A pass counts as `full` width when any main epoch ran its validator
        at full width (a cold adaptive pool, no estimate yet, an unbounded
        master) or it was retried — the overflowed capped attempt is the
        cost of the full-width path — else `capped`."""
        obs = self.obs
        t0 = _obs_now() if obs is not None else 0.0
        cap_warm, cap_rest, n_warm = self._plan_caps(cold)
        with span("engine.dispatch", obs, cat="engine"):
            res = self._launch(pool, x, state, cap_warm, cap_rest, n_warm,
                               n_bootstrap, mesh)
        self.cap_history.append(cap_rest)
        if not self.adaptive and obs is None:
            return res
        with span("engine.stats_wait", obs, cat="engine"):
            stats = jax.device_get(res.stats)
        full = self.pb in (effective_cap(cap_warm, self.pb),
                           effective_cap(cap_rest, self.pb))
        if self.adaptive and cap_rest is not None \
                and np.any(stats.proposed > stats.cap):
            self.n_cap_retries += 1
            self._cap_est = None       # estimate was wrong: reset wide
            self.cap_history[-1] = None   # committed pass ran full-width
            full = True
            with span("engine.retry", obs, cat="engine"):
                res = self._launch(pool, x, state, None, None, 0,
                                   n_bootstrap, mesh)
                stats = jax.device_get(res.stats)
        self._observe_stats(stats, cold)
        if obs is not None:
            self._export_pass(stats, t0, "full" if full else "capped")
        return res

    # ------------------------------------------------------------- batch
    def run(self, x: jnp.ndarray, *, pool: CenterPool | None = None,
            state: Any = None, n_bootstrap: int = 0) -> OCCPassResult:
        """One full pass over x as a single compiled call."""
        cold = pool is None
        if pool is None:
            # Initializer scope = the first Pb block: identical for one-shot
            # and streaming runs (and permutation-free: the data prefix).
            pool = self.txn.init_pool(x[:min(self.pb, x.shape[0])])
        if state is None:
            state = self.txn.make_state(x, 0)
        res = self._dispatch(pool, x, state,
                             n_bootstrap=min(int(n_bootstrap), x.shape[0]),
                             cold=cold, mesh=self.mesh)
        if self.publish is not None:
            with span("engine.publish", self.obs, cat="engine"):
                self.publish(res, n_seen=x.shape[0],
                             epochs=res.stats.proposed.shape[0],
                             cap_est=self._cap_est)
        return res

    def refine(self, pool: CenterPool, x: jnp.ndarray, assign: Any) -> CenterPool:
        return self.txn.refine(pool, x, assign)

    # ------------------------------------------- pluggable proposal source
    def local_proposer(self):
        """The in-process proposal source: jitted `txn.propose` on the full
        epoch.  `run_from_proposals(x)` with this source is the reference
        the cluster driver's bit-identity audit compares against (and a
        worker's jitted shard propose equals the matching slice of this —
        jit-to-jit exactness is what makes the cluster bitwise faithful)."""
        def propose_fn(pool, x_e, state_e, valid_e, *, epoch, offset):
            send, payload, aux, safe = _propose_epoch_jit(
                self.txn, pool, x_e, state_e)
            return send, payload, aux, safe, valid_e
        return propose_fn

    def run_from_proposals(self, x: jnp.ndarray, propose_fn=None, *,
                           pool: CenterPool | None = None, state: Any = None,
                           n_bootstrap: int = 0, on_commit=None,
                           on_outputs=None,
                           epoch_base: int = 0) -> OCCPassResult:
        """One pass with a PLUGGABLE proposal source — the host-driven dual
        of `run()`, bit-identical to it on the same data.

        Where `run()` fuses propose+validate into one compiled scan,
        this drives the epoch loop from Python and asks `propose_fn` for
        each epoch's proposal block; only the serializing finish
        (`_finish_epoch`: THE validator + writeback) runs here.  That is
        exactly the paper's master: proposals may come from anywhere —
        `local_proposer()` (in-process reference), or P worker processes
        each running `propose` on a disjoint shard with the blocks
        reassembled in global index order (`launch/occ_cluster.py`).

        propose_fn(pool, x_e, state_e, valid_e, *, epoch, offset) returns
        (send, payload, aux, safe, valid_e) for the epoch's `pb` points
        (`offset` is the global index of the epoch's first point; the
        returned valid_e may narrow the input mask, e.g. masking the shard
        of a worker that died mid-epoch).

        on_commit(pool, epoch, t_epochs), when given, runs after each main
        epoch's commit — the per-epoch replication hook: the cluster driver
        publishes the pool delta to followers here, so replication is
        per-epoch exactly as in the paper, not per-pass.

        on_outputs(epoch, assign_e, send_e, stats_e), when given, also runs
        after each main epoch — BEFORE on_commit, so a master that dies
        inside its commit hook has already exported the epoch — with that
        epoch's raw (still padded) assignment block, send mask, and
        (proposed, accepted, cap) scalars.  The §14 audit hook: a
        crash-recovery driver digests per-epoch outputs so runs that cross
        a promotion can be compared bit-for-bit against an uninterrupted
        reference.

        epoch_base shifts the epoch indices reported to propose_fn /
        on_commit / on_outputs (and nothing else): a promoted master that
        resumes from commit watermark v passes the REMAINING points with
        epoch_base=v, so global epoch numbering — and therefore worker
        shard addressing and publish version numbering — continues
        exactly where the dead master stopped.  Offsets stay relative to
        the x of THIS call.

        Adaptive caps need the fused pass's observe/retry machinery and the
        mesh path shards inside the compiled scan; both are refused here.
        Per-epoch dispatches are counted in `n_dispatches` (one per epoch —
        the price of a host-driven loop; `run()` stays 1 per pass).
        """
        if self.adaptive:
            raise ValueError("run_from_proposals requires a fixed/None "
                             "validate_cap (adaptive needs the fused pass)")
        if self.mesh is not None:
            raise ValueError("run_from_proposals is host-driven; use run() "
                             "for mesh-sharded passes")
        if propose_fn is None:
            propose_fn = self.local_proposer()
        cap = self.validate_cap
        n, d = x.shape
        nb = min(int(n_bootstrap), n)
        if pool is None:
            pool = self.txn.init_pool(x[:min(self.pb, n)])
        if state is None:
            state = self.txn.make_state(x, 0)

        obs = self.obs

        # Serial bootstrap prefix: width-1 epochs, stats discarded and send
        # forced True — exactly the fused pass's bootstrap scan.
        assign_parts = []
        for i in range(nb):
            xe = x[i:i + 1]
            se = jax.tree.map(lambda s: s[i:i + 1], state)
            ve = jnp.ones((1,), bool)
            s_, p_, a_, sf_, ve = propose_fn(pool, xe, se, ve,
                                             epoch=0, offset=i)
            pool, (ae, _, _, _, _) = _finish_epoch_jit(
                self.txn, pool, s_, p_, a_, sf_, ve, validate_cap=cap)
            self.n_dispatches += 1
            assign_parts.append(ae)
        assign_b = None if not nb else jax.tree.map(
            lambda *p: jnp.concatenate(p, 0), *assign_parts)

        # Main epochs: identical padding/valid-masking to the fused pass.
        n_rest = n - nb
        t_epochs = block_epochs(n_rest, self.pb)
        pad = t_epochs * self.pb - n_rest
        flat = lambda a: jnp.concatenate(
            [a[nb:], jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
        xs = flat(x)
        valid = flat(jnp.ones((n,), bool))
        ss = jax.tree.map(flat, state)

        am_parts, sm_parts, sent_l, acc_l, cap_l = [], [], [], [], []
        for e in range(t_epochs):
            ge = epoch_base + e          # global epoch index (§14 resume)
            t0e = _obs_now() if obs is not None else 0.0
            cut = slice(e * self.pb, (e + 1) * self.pb)
            with span("engine.propose", obs, cat="engine", epoch=ge):
                s_, p_, a_, sf_, ve = propose_fn(
                    pool, xs[cut], jax.tree.map(lambda s: s[cut], ss),
                    valid[cut], epoch=ge, offset=nb + e * self.pb)
            with span("engine.validate", obs, cat="engine", epoch=ge):
                pool, (ae, sde, ns, na, ce) = _finish_epoch_jit(
                    self.txn, pool, s_, p_, a_, sf_, ve, validate_cap=cap)
            self.n_dispatches += 1
            am_parts.append(ae)
            sm_parts.append(sde)
            sent_l.append(ns)
            acc_l.append(na)
            cap_l.append(ce)
            if obs is not None:
                # Host-driven loop: REAL per-epoch telemetry (the fused
                # pass has none on the host; its scopes split the device
                # time).
                nsi, nai, cei = int(ns), int(na), int(ce)
                m = obs.metrics
                m.counter("engine_epochs").inc()
                m.counter("engine_proposed").inc(nsi)
                m.counter("engine_accepted").inc(nai)
                m.counter("engine_rejected").inc(nsi - nai)
                if nsi:
                    m.gauge("engine_conflict_rate").set((nsi - nai) / nsi)
                m.gauge("engine_cap").set(cei)
                t1e = _obs_now()
                m.histogram("engine_epoch_s").observe(t1e - t0e)
                if obs.tracer is not None:
                    obs.tracer.complete(
                        "engine.epoch", t0e * 1e6, (t1e - t0e) * 1e6,
                        cat="engine",
                        args=dict(epoch=ge, proposed=nsi, accepted=nai,
                                  cap=cei))
            if on_outputs is not None:
                on_outputs(ge, ae, sde, (ns, na, ce))
            if on_commit is not None:
                on_commit(pool, ge, t_epochs)

        unpad = lambda a: a[:n_rest]
        assign = jax.tree.map(
            lambda *p: unpad(jnp.concatenate(p, 0)), *am_parts)
        send = unpad(jnp.concatenate(sm_parts, 0))
        if nb:
            assign = jax.tree.map(lambda b, m: jnp.concatenate([b, m], 0),
                                  assign_b, assign)
            send = jnp.concatenate([jnp.ones((nb,), bool), send], 0)
        epoch_of = jnp.concatenate([
            jnp.zeros((nb,), jnp.int32),
            jnp.repeat(jnp.arange(t_epochs, dtype=jnp.int32),
                       self.pb)[:n_rest]])
        res = OCCPassResult(pool, assign, send, epoch_of,
                            OCCStats(proposed=jnp.stack(sent_l),
                                     accepted=jnp.stack(acc_l),
                                     cap=jnp.stack(cap_l)))
        if self.publish is not None:
            with span("engine.publish", obs, cat="engine"):
                self.publish(res, n_seen=n, epochs=t_epochs,
                             cap_est=self._cap_est)
        return res

    # --------------------------------------------------------- streaming
    @property
    def pool(self) -> CenterPool | None:
        """Current streaming pool (None before the first committed epoch —
        initialization is deferred so data-statistic initializers see the
        first EPOCH, not the first arriving batch)."""
        return self._pool

    @property
    def n_seen(self) -> int:
        """Total points submitted to the stream (including carried ones)."""
        return self._n_seen

    @property
    def n_pending(self) -> int:
        """Points held in the partial-epoch carry, not yet in the pool."""
        return 0 if self._carry_x is None else int(self._carry_x.shape[0])

    @property
    def n_processed(self) -> int:
        """Points whose epoch has been committed to the pool."""
        return self._n_seen - self.n_pending

    @property
    def epochs_done(self) -> int:
        """Global epochs committed so far (the stream's epoch counter)."""
        return self._epoch_base

    @property
    def stats(self) -> OCCStats:
        """All streaming epochs' stats so far, concatenated on device.

        Chunks are consolidated into one array pair on read, so repeated
        reads stay O(1) and the retained list never grows unboundedly."""
        if not self._stat_chunks:
            z = jnp.zeros((0,), jnp.int32)
            return OCCStats(z, z, z)
        if len(self._stat_chunks) > 1:
            self._stat_chunks = [accumulate_pass_stats(self._stat_chunks)]
        return self._stat_chunks[0]

    def reset_stream(self) -> None:
        self._pool, self._n_seen, self._stat_chunks = None, 0, []
        self._epoch_base = 0
        self._carry_x = self._carry_state = None

    def restore(self, snapshot, *, k_max: int) -> None:
        """Resume a stream from a published `serving.ModelSnapshot`.

        Seeds the pool (re-expanded to the trainer's (k_max, D) buffer —
        rows beyond `count` are zero, exactly as in the live pool), the
        global point/epoch counters, AND the adaptive-cap estimator the
        snapshot persisted (`cap_est`), so the restored stream's very
        first pass runs at the warm Thm-3.3 cap instead of paying a
        full-width burn-in pass.  The stream continues from the snapshot's
        `n_seen` — points after the last publish (a pending carry at crash
        time) must be re-sent by the caller.  A restored stream is
        bit-identical to the uninterrupted one from the restore point on
        (adaptive caps never change results — §11's full-width retry)."""
        if self._pool is not None or self._n_seen:
            raise ValueError("restore() requires a fresh engine/stream")
        self._pool = snapshot.to_pool(k_max)
        self._n_seen = snapshot.n_seen
        self._epoch_base = snapshot.epochs
        if self.adaptive and snapshot.cap_est is not None:
            self._cap_est = snapshot.cap_est

    def _empty_stream_result(self, x1: jnp.ndarray, s1: Any) -> OCCPassResult:
        """A zero-point OCCPassResult (pool unchanged, length-0 outputs).

        Returned when a whole batch lands in the partial-epoch carry.  The
        output leaf shapes/dtypes are transaction-specific (DP/OFL: (N,)
        int32; BP: (N, K_max) bool), so they are derived ONCE by shape-only
        tracing of the pass on the carried points — no compute, no dispatch
        — and cached per point shape/dtype: fine-grained streams (arrival
        in sub-pb batches) must not pay a Python re-trace per carry-only
        call.  Before the first commit (no pool yet) the result carries an
        all-zeros pool of the right shape: nothing is in the pool, and the
        initializer must not run until its epoch's points are known.
        """
        key = (x1.shape[1:], str(x1.dtype))
        cached = self._empty_templates.get(key)
        if cached is not None:
            pool = self._pool if self._pool is not None else cached.pool
            return cached._replace(pool=pool)
        global _PASS_TRACES
        traces = _PASS_TRACES          # eval_shape traces without compiling;
        try:                           # don't count it as a compilation
            pool_sd = jax.eval_shape(self.txn.init_pool, x1)
            zero_pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                     pool_sd)
            sd = jax.eval_shape(
                lambda p, x, s: _engine_pass(
                    self.txn, p, x, s, pb=self.pb,
                    cap_warm=self.validate_cap, cap_rest=self.validate_cap,
                    n_warm=0, n_bootstrap=0, mesh=None,
                    data_axis=self.data_axis),
                zero_pool, x1, s1)
        finally:
            _PASS_TRACES = traces
        empty = lambda s: jnp.zeros((0,) + s.shape[1:], s.dtype)
        # Cache with the NEUTRAL zero pool (a template must not capture the
        # live stream's state — reset_stream would otherwise leak the old
        # pool into a fresh stream's pre-commit results); the caller's
        # current pool is substituted at return time above.
        res = OCCPassResult(
            zero_pool, jax.tree.map(empty, sd.assign), empty(sd.send),
            empty(sd.epoch_of),
            OCCStats(empty(sd.stats.proposed), empty(sd.stats.accepted),
                     empty(sd.stats.cap)))
        self._empty_templates[key] = res
        if self._pool is not None:
            return res._replace(pool=self._pool)
        return res

    def _commit_stream_pass(self, xb: jnp.ndarray, state: Any) -> OCCPassResult:
        """Run one compiled pass over pb-aligned (or final-flush) points and
        fold it into the stream: pool, stats, global epoch numbering,
        publication.  The first commit initializes the pool from ITS first
        epoch's points — the same points the one-shot run's initializer
        sees, so streams are bit-identical even for data-statistic inits."""
        cold = self._pool is None
        if cold:
            self._pool = self.txn.init_pool(xb[:min(self.pb, xb.shape[0])])
        res = self._dispatch(self._pool, xb, state, n_bootstrap=0,
                             cold=cold, mesh=self.mesh)
        self._pool = res.pool
        self._stat_chunks.append(res.stats)
        if len(self._stat_chunks) >= 64:
            _ = self.stats          # consolidate chunks on long streams
        res = res._replace(epoch_of=res.epoch_of + self._epoch_base)
        self._epoch_base += res.stats.proposed.shape[0]
        if self.publish is not None:
            with span("engine.publish", self.obs, cat="engine"):
                self.publish(res, n_seen=self.n_processed,
                             epochs=self._epoch_base, cap_est=self._cap_est)
        return res

    def partial_fit(self, xb: jnp.ndarray, *, state: Any = None,
                    pool: CenterPool | None = None) -> OCCPassResult:
        """Incremental epochs over an arriving batch (online serving mode).

        The batch is processed against the pool accumulated so far; the
        pool, the count of points seen, and the epoch statistics carry over
        to the next call.  Per-point state is derived from the global point
        index (`make_state(xb, n_seen)`), so e.g. OCC-OFL's counter-based
        uniforms match a one-shot run over the concatenated stream.

        Epoch boundaries are bit-identical to the one-shot run for ANY
        batch length: the trailing `n mod pb` points are held in an
        explicit partial-epoch carry (`n_pending`) and processed when the
        epoch fills in a later call — or by `flush()` at stream end, which
        commits them as the one-shot run's final short epoch.  The returned
        OCCPassResult therefore covers the points *committed* by this call
        (carried points first, then the aligned prefix of this batch);
        concatenating every call's `assign` plus `flush()`'s reproduces the
        one-shot assignment exactly.  `epoch_of` is globally numbered
        across the stream.  A call that only grows the carry returns a
        zero-point result with the pool unchanged.

        Pool initialization is deferred to the first committed epoch and
        computed from its points — exactly the points the one-shot run's
        initializer sees — so even data-statistic initializers (BP-means
        `init_mean`) are batching-independent.  `pool` (first call only)
        still seeds the stream with an explicit initial pool, e.g. a warm
        model restored from a snapshot.

        The call is the host span `engine.partial_fit`; inside it
        `engine.state` (the per-point state: `make_state` and the carry's
        state joined ahead of it), `engine.dispatch`, `engine.stats_wait`,
        `engine.retry` and `engine.publish` (`_dispatch`,
        `_commit_stream_pass`) mark what the host does while the device
        runs or idles.
        """
        with span("engine.partial_fit", self.obs, cat="engine"):
            if pool is not None:
                if self._pool is not None:
                    raise ValueError("pool= only seeds the FIRST partial_fit")
                self._pool = pool
            with span("engine.state", self.obs, cat="engine"):
                if state is None:
                    state = self.txn.make_state(xb, self._n_seen)
                if self._carry_x is not None:
                    state = jax.tree.map(_join_state, self._carry_state,
                                         state)
            self._n_seen += xb.shape[0]
            if self._carry_x is not None:
                xb = jnp.concatenate([self._carry_x, xb], 0)
            n = xb.shape[0]
            n_full = (n // self.pb) * self.pb
            if n_full < n:
                self._carry_x = xb[n_full:]
                self._carry_state = jax.tree.map(lambda s: s[n_full:], state)
            else:
                self._carry_x = self._carry_state = None
            if n_full == 0:
                return self._empty_stream_result(xb, state)
            xb = xb[:n_full]
            state = jax.tree.map(lambda s: s[:n_full], state)
            return self._commit_stream_pass(xb, state)

    def flush(self) -> OCCPassResult | None:
        """Commit the carried partial epoch as the stream's final short
        epoch (exactly the one-shot run's last epoch).  Returns that
        result, or None when nothing is pending."""
        if self._carry_x is None:
            return None
        xb, state = self._carry_x, self._carry_state
        self._carry_x = self._carry_state = None
        return self._commit_stream_pass(xb, state)
