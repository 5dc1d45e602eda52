"""The unified precomputed validator (DESIGN.md §11) vs the reference
implementations — for DP-means, OFL, and BP-means, across random epochs,
caps, pool occupancies, and the sent_overflow / pool-overflow paths.

Contracts enforced here:
  * DP-means / OFL payload scan is bit-identical to the legacy per-step
    D-dimensional recompute (`core/_reference.py`), including pool bits.
  * The log-depth fixed point the engine resolves DP-means / OFL epochs
    by is bit-identical to the serial payload scan — slots, refs, count
    and overflow of every epoch (min/compare algebra never rounds); the
    two functions are called directly on each epoch of the engine's pass.
  * BP-means Gram-carry validation is decision-identical to the
    D-dimensional refit reference — every discrete output (assignments,
    sends, slots, counts, stats, overflow) bit-equal — with appended
    centers equal up to float reassociation of the same exact algebra
    (§11), asserted at ulp-scale tolerance.
  * `validate_cap="adaptive"` commits results bit-identical to the
    unbounded master for all three transactions (the overflow-retry
    guarantee).

Two layers: a deterministic seeded sweep that always runs, and hypothesis
property variants (skipped when hypothesis is absent) exploring the same
space adversarially.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BPMeansTransaction, DPMeansTransaction, OCCEngine, OFLTransaction,
    logdepth_validate, make_pool, nearest_center, precomputed_gather_validate,
    precomputed_validate,
)
from repro.core._reference import _reference_validate, reference_pass
from repro.core.occ import _compact_sent, effective_cap

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _seeded_pool(k_max, d, k0, rng):
    """A pool with k0 occupied slots (random occupancy ≤ k_max)."""
    pool = make_pool(k_max, d)
    if k0:
        centers = pool.centers.at[:k0].set(
            jnp.asarray(rng.normal(size=(k0, d)).astype(np.float32) * 2.0))
        pool = pool._replace(centers=centers,
                             mask=pool.mask.at[:k0].set(True),
                             count=jnp.asarray(k0, jnp.int32))
    return pool


def _problem(n, d, k_max, k0, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) * 2.0)
    return x, _seeded_pool(k_max, d, min(k0, k_max), rng)


def _assert_matches_reference(txn, x, pool, pb, cap, state=None):
    """Engine (fast path) == legacy per-step reference, bit for bit."""
    fast = OCCEngine(txn, pb, validate_cap=cap).run(x, pool=pool,
                                                    state=state)
    rp, ra, rs, rst = reference_pass(txn, pool, x, state=state, pb=pb,
                                     cap=cap)
    np.testing.assert_array_equal(np.asarray(fast.assign), np.asarray(ra))
    np.testing.assert_array_equal(np.asarray(fast.send), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(fast.stats.proposed),
                                  np.asarray(rst.proposed))
    np.testing.assert_array_equal(np.asarray(fast.stats.accepted),
                                  np.asarray(rst.accepted))
    np.testing.assert_array_equal(np.asarray(fast.pool.centers),
                                  np.asarray(rp.centers))
    np.testing.assert_array_equal(np.asarray(fast.pool.mask),
                                  np.asarray(rp.mask))
    assert int(fast.pool.count) == int(rp.count)
    assert bool(fast.pool.overflow) == bool(rp.overflow)
    return fast


@functools.partial(jax.jit, static_argnames="cap")
def _resolve_both(txn, pool, send, payload, aux, cap):
    """One epoch's compacted accept chain, resolved by the serial payload
    scan and by the log-depth fixed point: two (slots, refs, count,
    overflow) tuples."""
    order, _ = _compact_sent(send, effective_cap(cap, send.shape[0]))
    pre = txn.precompute_accept(pool, payload[order],
                                jax.tree.map(lambda a: a[order], aux),
                                pool.count)
    return (precomputed_validate(pool, send[order], pre, txn.accept_pre),
            logdepth_validate(pool, send[order], pre, txn.accept_pre))


def _assert_epoch_resolutions_identical(txn, pool, send, payload, aux, cap):
    serial, logd = _resolve_both(txn, pool, send, payload, aux, cap)
    for got, want in zip(logd, serial):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _assert_resolutions_identical(txn, x, pool, pb, cap, state=None):
    """logdepth == serial, bit for bit, on every epoch of the engine's own
    pass (its host-driven dual, which hands each epoch's starting pool to
    the proposal source)."""
    eng = OCCEngine(txn, pb, validate_cap=cap)
    propose = eng.local_proposer()

    def checked(pool, x_e, state_e, valid_e, **at):
        send, payload, aux, safe, valid_e = propose(pool, x_e, state_e,
                                                    valid_e, **at)
        _assert_epoch_resolutions_identical(
            txn, pool, jnp.logical_and(send, valid_e), payload, aux, cap)
        return send, payload, aux, safe, valid_e

    return eng.run_from_proposals(x, checked, pool=pool, state=state)


def _assert_bp_decision_identical(txn, x, pool, pb, cap):
    """BP Gram scan vs D-dim refit reference: every discrete output bit-
    identical; centers exact-algebra-equal (ulp-scale reassociation only)."""
    z0 = txn.make_state(x)
    fast = OCCEngine(txn, pb, validate_cap=cap).run(x, pool=pool, state=z0)
    rp, ra, rs, rst = reference_pass(txn, pool, x, state=z0, pb=pb, cap=cap)
    np.testing.assert_array_equal(np.asarray(fast.assign), np.asarray(ra))
    np.testing.assert_array_equal(np.asarray(fast.send), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(fast.stats.proposed),
                                  np.asarray(rst.proposed))
    np.testing.assert_array_equal(np.asarray(fast.stats.accepted),
                                  np.asarray(rst.accepted))
    np.testing.assert_array_equal(np.asarray(fast.pool.mask),
                                  np.asarray(rp.mask))
    assert int(fast.pool.count) == int(rp.count)
    assert bool(fast.pool.overflow) == bool(rp.overflow)
    scale = max(1.0, float(jnp.max(jnp.abs(rp.centers))))
    np.testing.assert_allclose(np.asarray(fast.pool.centers),
                               np.asarray(rp.centers), atol=1e-5 * scale)
    return fast


# ------------------------------------------------- deterministic seeded sweep

SWEEP = [
    # (n, d, k_max, k0, pb, lam, cap)
    (48, 3, 16, 0, 8, 2.0, None),       # cold pool, unbounded master
    (48, 3, 16, 5, 8, 2.0, 16),         # warm pool, roomy cap
    (96, 5, 64, 8, 16, 0.8, 4),         # small lam + tiny cap: sent_overflow
    (24, 2, 16, 2, 32, 4.0, 4),         # epoch wider than data
    (96, 5, 8, 0, 16, 0.5, None),       # pool-capacity overflow path
]


@pytest.mark.parametrize("n,d,k_max,k0,pb,lam,cap", SWEEP)
def test_dpmeans_fast_equals_reference_sweep(n, d, k_max, k0, pb, lam, cap):
    x, pool = _problem(n, d, k_max, k0, seed=n + k0)
    _assert_matches_reference(DPMeansTransaction(lam, k_max), x, pool, pb, cap)


@pytest.mark.parametrize("n,d,k_max,k0,pb,lam,cap", SWEEP)
def test_ofl_fast_equals_reference_sweep(n, d, k_max, k0, pb, lam, cap):
    x, pool = _problem(n, d, k_max, k0, seed=n + k0)
    txn = OFLTransaction(lam, k_max, jax.random.key(n))
    _assert_matches_reference(txn, x, pool, pb, cap)


@pytest.mark.parametrize("n,d,k_max,k0,pb,lam,cap", SWEEP)
def test_dpmeans_logdepth_equals_serial_sweep(n, d, k_max, k0, pb, lam, cap):
    x, pool = _problem(n, d, k_max, k0, seed=n + k0)
    _assert_resolutions_identical(DPMeansTransaction(lam, k_max), x, pool,
                                  pb, cap)


@pytest.mark.parametrize("n,d,k_max,k0,pb,lam,cap", SWEEP)
def test_ofl_logdepth_equals_serial_sweep(n, d, k_max, k0, pb, lam, cap):
    x, pool = _problem(n, d, k_max, k0, seed=n + k0)
    txn = OFLTransaction(lam, k_max, jax.random.key(n))
    _assert_resolutions_identical(txn, x, pool, pb, cap)


def test_ofl_logdepth_equals_serial_at_the_cell_shape():
    """deep96-ofl.train's validator shape: width-256 epochs at D = 96,
    λ = 1.6, per-point uniforms, against a pool of 2^12 live facilities in
    2^14 slots.  A quarter of the points come from 64 components the pool
    lacks, so each epoch sends about a fifth of its points and some sends
    kill later ones: the fixed point takes more than one round."""
    d, k_max, k0, n, pb = 96, 1 << 14, 1 << 12, 1024, 256
    rng = np.random.default_rng(16)
    unit = lambda a: a / np.linalg.norm(a, axis=1, keepdims=True)
    means = unit(rng.normal(size=(k0 + 64, d)).astype(np.float32))
    z = np.where(rng.random(n) < 0.25, k0 + rng.integers(0, 64, n),
                 rng.integers(0, k0, n))
    x = jnp.asarray(unit(means[z] + np.sqrt(0.5 / d) * rng.normal(
        size=(n, d)).astype(np.float32)))
    pool = make_pool(k_max, d)
    pool = pool._replace(centers=pool.centers.at[:k0].set(means[:k0]),
                         mask=pool.mask.at[:k0].set(True),
                         count=jnp.asarray(k0, jnp.int32))
    txn = OFLTransaction(1.6, k_max, jax.random.key(7))
    res = _assert_resolutions_identical(txn, x, pool, pb, None,
                                        state=txn.make_state(x))
    sent, acc = np.asarray(res.stats.proposed), np.asarray(res.stats.accepted)
    assert (np.asarray(res.stats.cap) == pb).all()
    assert (sent > pb // 8).all() and (sent < pb // 2).all(), sent
    assert (sent > acc).any(), (sent, acc)


@pytest.mark.parametrize("width", [1, 8, 64])
def test_payload_epochs_resolve_by_the_fixed_point(width):
    """At every compaction width, the width-1 epochs of a bootstrap prefix
    included, a payload epoch resolves by the log-depth fixed point (a
    `while` of no fixed trip count), with the serial scan only as the
    pool-overflow branch of its `conditional`."""
    txn = DPMeansTransaction(1.0, 64)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(64, 4)).astype(np.float32))
    pool = make_pool(64, 4)
    send, payload, aux, _ = txn.propose(pool, x, ())
    fn = jax.jit(lambda p, s, pl, a: precomputed_gather_validate(
        p, s, pl, a, txn.precompute_accept, txn.accept_pre, cap=width))
    text = fn.lower(pool, send, payload, aux).compile().as_text()
    loops = [ln for ln in text.splitlines() if re.search(r" while\(", ln)]
    assert "conditional" in text
    assert any("known_trip_count" not in ln for ln in loops), loops


@pytest.mark.parametrize("n,d,k_max,k0,pb,lam,cap", SWEEP)
def test_bpmeans_gram_matches_refit_reference_sweep(n, d, k_max, k0, pb, lam,
                                                    cap):
    """The sweep's rows 3 and 5 drive sent_overflow and pool-capacity
    overflow through the Gram scan (small λ floods the validator)."""
    x, pool = _problem(n, d, k_max, k0, seed=n + k0)
    txn = BPMeansTransaction(lam, k_max, init_mean=False)
    _assert_bp_decision_identical(txn, x, pool, pb, cap)


@pytest.mark.parametrize("txn_name", ["dp", "ofl", "bp"])
def test_adaptive_cap_equals_full_cap(txn_name):
    """Adaptive committed results are bit-identical to the unbounded master
    for all three transactions — multi-pass so the Thm-3.3 estimate
    actually engages after the burn-in pass."""
    x, _ = _problem(256, 4, 128, 0, seed=17)
    if txn_name == "dp":
        txn = DPMeansTransaction(3.0, 128)
    elif txn_name == "ofl":
        txn = OFLTransaction(3.0, 128, jax.random.key(3))
    else:
        txn = BPMeansTransaction(3.0, 128, init_mean=False)
    state = txn.make_state(x)
    ea = OCCEngine(txn, pb=64, validate_cap="adaptive")
    ef = OCCEngine(txn, pb=64)
    ra, rf = ea.run(x, state=state), ef.run(x, state=state)
    for _ in range(2):          # warm passes: the shrunken cap is live now
        ra = ea.run(x, pool=ra.pool, state=state)
        rf = ef.run(x, pool=rf.pool, state=state)
    assert ea.cap_history[-1] is not None and ea.cap_history[-1] < 64, \
        f"adaptive cap never engaged: {ea.cap_history}"
    np.testing.assert_array_equal(np.asarray(ra.assign), np.asarray(rf.assign))
    np.testing.assert_array_equal(np.asarray(ra.pool.centers),
                                  np.asarray(rf.pool.centers))
    np.testing.assert_array_equal(np.asarray(ra.stats.proposed),
                                  np.asarray(rf.stats.proposed))
    assert int(ra.pool.count) == int(rf.pool.count)
    # the chosen cap is surfaced per epoch
    caps = np.asarray(ra.stats.cap)
    assert caps.shape == ra.stats.proposed.shape
    assert (caps >= np.asarray(ra.stats.proposed)).all()


def test_adaptive_cap_overflow_retry_is_lossless():
    """A stream whose conflict rate explodes after a quiet prefix overflows
    the shrunken window; the engine must re-dispatch at full width and
    commit results identical to the unbounded master."""
    rng = np.random.default_rng(11)
    quiet = rng.normal(size=(192, 4)).astype(np.float32) * 0.1
    burst = rng.normal(size=(64, 4)).astype(np.float32) * 50.0
    x = jnp.asarray(np.concatenate([quiet, burst]))
    txn = DPMeansTransaction(2.0, 256)
    ea = OCCEngine(txn, pb=64, validate_cap="adaptive")
    ef = OCCEngine(txn, pb=64)
    za, zf = [], []
    for lo in range(0, 256, 64):
        za.append(np.asarray(ea.partial_fit(x[lo:lo + 64]).assign))
        zf.append(np.asarray(ef.partial_fit(x[lo:lo + 64]).assign))
    assert ea.n_cap_retries >= 1, ea.cap_history
    np.testing.assert_array_equal(np.concatenate(za), np.concatenate(zf))
    np.testing.assert_array_equal(np.asarray(ea.pool.centers),
                                  np.asarray(ef.pool.centers))
    assert int(ea.pool.count) == int(ef.pool.count)


def test_unknown_knobs_raise():
    with pytest.raises(ValueError):
        OCCEngine(DPMeansTransaction(1.0, 8), 8, validate_cap="nope")


def test_sent_overflow_bitidentical_slots():
    """Direct occ-level check: slots / outs / overflow from the fast path
    match the reference path through the bounded master, cap exceeded."""
    rng = np.random.default_rng(0)
    d, k_max, cap = 3, 16, 3
    pool = _seeded_pool(k_max, d, 2, rng)
    x = jnp.asarray(rng.normal(size=(10, d)).astype(np.float32) * 10.0)
    txn = DPMeansTransaction(1.0, k_max)
    send, payload, aux, _ = txn.propose(pool, x, ())
    count0 = pool.count

    accept = lambda p, v_j, a_j: txn.accept(p, v_j, a_j, count0)
    pl_, sl_, ol_, ovf_l = _reference_validate(pool, send, payload, accept,
                                               aux, cap=cap)
    pf_, sf_, of_, ovf_f = precomputed_gather_validate(
        pool, send, payload, aux, txn.precompute_accept, txn.accept_pre,
        cap=cap)
    assert bool(ovf_l) and bool(ovf_f)
    np.testing.assert_array_equal(np.asarray(sl_), np.asarray(sf_))
    # outs only carry meaning for sent proposals (writeback masks them)
    s = np.asarray(send)
    np.testing.assert_array_equal(np.asarray(ol_)[s], np.asarray(of_)[s])
    np.testing.assert_array_equal(np.asarray(pl_.centers),
                                  np.asarray(pf_.centers))
    assert int(pl_.count) == int(pf_.count)
    _assert_epoch_resolutions_identical(txn, pool, send, payload, aux, cap)


def test_fast_path_equals_full_recompute_reference():
    """Three-way: the precomputed path also matches the ORIGINAL
    full-recompute accept rule (nearest_center over the whole pool each
    scan step) — the pre-threading reference implementation."""
    rng = np.random.default_rng(3)
    d, k_max = 4, 32
    pool = _seeded_pool(k_max, d, 5, rng)
    x = jnp.asarray(rng.normal(size=(40, d)).astype(np.float32) * 2.0)
    lam2 = jnp.float32(2.0) ** 2
    txn = DPMeansTransaction(2.0, k_max)
    send, payload, aux, _ = txn.propose(pool, x, ())

    def full_recompute(p, x_j, a_j):
        d2, ref = nearest_center(p, x_j)
        return d2 > lam2, x_j, ref

    pr, sr, orr, _ = _reference_validate(pool, send, payload, full_recompute,
                                         aux=None, cap=None)
    pf, sf, off, _ = precomputed_gather_validate(
        pool, send, payload, aux, txn.precompute_accept, txn.accept_pre,
        cap=None)
    np.testing.assert_array_equal(np.asarray(sr), np.asarray(sf))
    s = np.asarray(send)
    np.testing.assert_array_equal(np.asarray(orr)[s], np.asarray(off)[s])
    np.testing.assert_array_equal(np.asarray(pr.centers), np.asarray(pf.centers))
    assert int(pr.count) == int(pf.count)


# ------------------------------------------------- hypothesis property layer

if HAVE_HYPOTHESIS:
    SET = dict(max_examples=10, deadline=None)

    @st.composite
    def validator_problem(draw):
        n = draw(st.sampled_from([24, 48, 96]))
        d = draw(st.sampled_from([2, 5]))
        pb = draw(st.sampled_from([8, 16, 32]))
        lam = draw(st.floats(0.5, 5.0))
        k_max = draw(st.sampled_from([16, 64]))
        k0 = draw(st.integers(0, 8))
        # cap=4 routinely exercises sent_overflow; None = unbounded master
        cap = draw(st.sampled_from([None, 4, 16]))
        seed = draw(st.integers(0, 2 ** 16))
        x, pool = _problem(n, d, k_max, k0, seed)
        return x, pool, pb, float(lam), k_max, cap, seed

    @given(validator_problem())
    @settings(**SET)
    def test_dpmeans_fast_equals_reference_property(prob):
        x, pool, pb, lam, k_max, cap, _ = prob
        _assert_matches_reference(DPMeansTransaction(lam, k_max), x, pool,
                                  pb, cap)

    @given(validator_problem())
    @settings(**SET)
    def test_ofl_fast_equals_reference_property(prob):
        x, pool, pb, lam, k_max, cap, seed = prob
        txn = OFLTransaction(lam, k_max, jax.random.key(seed))
        _assert_matches_reference(txn, x, pool, pb, cap)

    @given(validator_problem())
    @settings(**SET)
    def test_logdepth_equals_serial_property(prob):
        x, pool, pb, lam, k_max, cap, seed = prob
        _assert_resolutions_identical(DPMeansTransaction(lam, k_max), x,
                                      pool, pb, cap)
        txn = OFLTransaction(lam, k_max, jax.random.key(seed))
        _assert_resolutions_identical(txn, x, pool, pb, cap)

    @given(validator_problem())
    @settings(max_examples=8, deadline=None)
    def test_bpmeans_gram_matches_reference_property(prob):
        """The ISSUE's bit-identity layer: every discrete BP validation
        output equals the D-dim refit reference on adversarial problems —
        including sent_overflow (cap=4 draws) and pool-overflow (k0 ~ k_max
        with small λ) epochs."""
        x, pool, pb, lam, k_max, cap, _ = prob
        txn = BPMeansTransaction(lam, k_max, init_mean=False)
        _assert_bp_decision_identical(txn, x, pool, pb, cap)

    @given(st.sampled_from([0.5, 0.8]), st.integers(0, 2 ** 16))
    @settings(max_examples=4, deadline=None)
    def test_bpmeans_gram_overflow_property(lam, seed):
        """Dedicated overflow hammer: tiny pool + tiny cap + flooding λ."""
        x, pool = _problem(96, 5, 8, 0, seed)
        txn = BPMeansTransaction(lam, 8, init_mean=False)
        res = _assert_bp_decision_identical(txn, x, pool, 16, 4)
        assert bool(res.pool.overflow)
else:  # pragma: no cover - exercised only without hypothesis
    def test_hypothesis_layer_skipped():
        pytest.skip("hypothesis not installed; deterministic sweep still ran")
