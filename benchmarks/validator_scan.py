"""Unified precomputed validator vs the legacy per-step recompute.

The legacy serializing validator (now `core/_reference.py`, tests/bench
only) does O(cap · K_max · D) *sequential* work per epoch: every scan step
recomputes distances — or, for BP-means, a full coordinate-pass refit —
against the full fixed-capacity pool and rewrites the (K_max, D) center
carry.  The engine path (DESIGN.md §9/§11) batches all D-dimensional work
into one MXU precompute and leaves a D-free serializing resolution.

Variants timed here, all on the SAME problem sizes:

  dp_reference / dp_precomputed  — the legacy pass vs the engine pass
                                   (payload accept chain by the §11
                                   log-depth fixed point)
  dp_serial / dp_logdepth        — the payload resolution in isolation:
                                   the serial scan vs the fixed point, on
                                   the same compacted epoch
  dp_adaptive                    — Thm-3.3 adaptive cap, post-burn-in pass
                                   (vs the same warm pass at full cap)
  bp_reference / bp_gram         — BP-means legacy refit vs Gram-carry scan

Each fast path is checked against its reference (bit-identical for DP,
decision-identical for BP) before timing, and the trajectory lands in
BENCH_validator.json with deltas vs the previous tracked record (the PR-2
baseline on first run after this refactor).

  PYTHONPATH=src python -m benchmarks.validator_scan
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BPMeansTransaction, DPMeansTransaction, OCCEngine, logdepth_validate,
    precomputed_gather_validate, precomputed_validate,
)
from repro.core._reference import _reference_validate, reference_pass
from repro.core.occ import block_epochs
from repro.data import dp_stick_breaking_data


def _time(fn, repeats):
    jax.block_until_ready(fn())              # warm the jit cache, fully
    best = float("inf")
    for _ in range(repeats):                 # min-of-repeats: robust to the
        t0 = time.time()                     # CI container's noisy wall clock
        jax.block_until_ready(fn())
        best = min(best, time.time() - t0)
    return best


def run(n: int = 2048, d: int = 256, k_max: int = 512, pb: int = 512,
        cap: int = 256, lam: float = 16.0, bp_lam: float = 14.0,
        repeats: int = 5, out_path: str | None = None, quiet: bool = False):
    x, _, _ = dp_stick_breaking_data(n, dim=d, seed=0)
    x = jnp.asarray(x)
    t_epochs = block_epochs(n, pb)
    rows = []

    # ---------------------------------------------------------- DP-means
    txn = DPMeansTransaction(lam, k_max=k_max)
    pool0 = txn.init_pool(x[:pb])
    eng_fast = OCCEngine(txn, pb, validate_cap=cap)
    rf = jax.block_until_ready(eng_fast.run(x))
    rp, ra, _, rst = reference_pass(txn, pool0, x, pb=pb, cap=cap)
    assert np.array_equal(np.asarray(rf.assign), np.asarray(ra))
    assert np.array_equal(np.asarray(rf.pool.centers), np.asarray(rp.centers))
    assert np.array_equal(np.asarray(rf.stats.proposed),
                          np.asarray(rst.proposed))

    ref_s = _time(lambda: reference_pass(txn, pool0, x, pb=pb, cap=cap)[0]
                  .centers, repeats)
    fast_s = _time(lambda: eng_fast.run(x).pool.centers, repeats)

    # The payload resolution in isolation, on epoch-1 inputs compacted to
    # the first `cap` sent proposals: the serial scan against the fixed
    # point the engine runs — the same four outputs, bit for bit.
    send, payload, aux, _ = jax.jit(txn.propose)(pool0, x[:pb], ())
    order = jnp.argsort(~send, stable=True)[:cap]
    send_c = send[order]
    pre = jax.jit(txn.precompute_accept)(
        pool0, payload[order], jax.tree.map(lambda a: a[order], aux),
        pool0.count)
    serial_step = jax.jit(lambda s, pre: precomputed_validate(
        pool0, s, pre, txn.accept_pre))
    logd_step = jax.jit(lambda s, pre: logdepth_validate(
        pool0, s, pre, txn.accept_pre))
    for got, want in zip(logd_step(send_c, pre), serial_step(send_c, pre)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    serial_s = _time(lambda: serial_step(send_c, pre)[0], repeats)
    logd_s = _time(lambda: logd_step(send_c, pre)[0], repeats)

    # Adaptive cap: time a WARM pass (the Thm-3.3 regime the cap targets —
    # epoch 1 of a cold pool always runs full-width by design).
    eng_ad = OCCEngine(txn, pb, validate_cap="adaptive")
    warm = eng_ad.run(x)                  # burn-in: observes the sent rate
    eng_ad.run(x, pool=warm.pool)         # warm pass: shrunken cap live
    cap_ad = eng_ad.cap_history[-1]
    ra2 = jax.block_until_ready(eng_ad.run(x, pool=warm.pool))
    rf2 = jax.block_until_ready(eng_fast.run(x, pool=warm.pool))
    assert np.array_equal(np.asarray(ra2.assign), np.asarray(rf2.assign))
    adapt_s = _time(lambda: eng_ad.run(x, pool=warm.pool).pool.centers,
                    repeats)
    full_warm_s = _time(lambda: eng_fast.run(x, pool=warm.pool).pool.centers,
                        repeats)
    assert eng_ad.n_cap_retries == 0

    # ---------------------------------------------------------- BP-means
    txb = BPMeansTransaction(bp_lam, k_max=k_max, init_mean=False)
    zb = txb.make_state(x)
    poolb = txb.init_pool(x[:pb])
    eng_bp = OCCEngine(txb, pb, validate_cap=cap)
    bf = jax.block_until_ready(eng_bp.run(x, state=zb))
    bp_ref, bra, _, brst = reference_pass(txb, poolb, x, state=zb, pb=pb,
                                          cap=cap)
    assert np.array_equal(np.asarray(bf.assign), np.asarray(bra))
    assert np.array_equal(np.asarray(bf.stats.proposed),
                          np.asarray(brst.proposed))
    assert int(bf.pool.count) == int(bp_ref.count)

    # The validator in isolation — the serialization point the §11 Gram
    # carry rewrites.  Epoch-1 inputs (cold pool: everything proposes, the
    # cap window saturates) are the heaviest serial load; propose cost is
    # identical on both paths and timed separately for context.
    prop_step = jax.jit(txb.propose)
    send_b, payload_b, aux_b, _ = prop_step(poolb, x[:pb], zb[:pb])
    count0_b = poolb.count
    acc_b = lambda p, v_j, a_j: txb.accept(p, v_j, a_j, count0_b)
    gram_step = jax.jit(lambda p, s, pay: precomputed_gather_validate(
        p, s, pay, None, txb.precompute_accept, txb.accept_pre, cap=cap))
    ref_step = jax.jit(lambda p, s, pay: _reference_validate(
        p, s, pay, acc_b, None, cap=cap))
    bp_ref_s = _time(lambda: ref_step(poolb, send_b, payload_b)[0].centers,
                     repeats)
    bp_gram_s = _time(lambda: gram_step(poolb, send_b, payload_b)[0].centers,
                      repeats)
    bp_prop_s = _time(lambda: prop_step(poolb, x[:pb], zb[:pb])[1], repeats)

    # Whole-pass wall clock (propose + validate + writeback, all epochs).
    bp_pass_ref_s = _time(lambda: reference_pass(
        txb, poolb, x, state=zb, pb=pb, cap=cap)[0].centers, repeats)
    bp_pass_gram_s = _time(lambda: eng_bp.run(x, state=zb).pool.centers,
                           repeats)

    record = {
        "bench": "validator_scan",
        "n": n, "d": d, "k_max": k_max, "pb": pb, "cap": cap,
        "t_epochs": t_epochs, "repeats": repeats,
        "dp_reference_wall_s": ref_s,
        "dp_precomputed_wall_s": fast_s,
        "dp_serial_validator_epoch_s": serial_s,
        "dp_logdepth_validator_epoch_s": logd_s,
        "dp_speedup": ref_s / fast_s,
        "dp_adaptive_wall_s": adapt_s,
        "dp_fullcap_warm_wall_s": full_warm_s,
        "dp_adaptive_speedup_after_epoch1": full_warm_s / adapt_s,
        "dp_adaptive_cap": cap_ad,
        "bp_reference_validator_epoch_s": bp_ref_s,
        "bp_gram_validator_epoch_s": bp_gram_s,
        "bp_validator_speedup": bp_ref_s / bp_gram_s,
        "bp_propose_epoch_s": bp_prop_s,
        "bp_reference_pass_wall_s": bp_pass_ref_s,
        "bp_gram_pass_wall_s": bp_pass_gram_s,
        "bp_pass_speedup": bp_pass_ref_s / bp_pass_gram_s,
        "bp_k": int(bf.pool.count),
        "reference_step_cost": "O(cap*K_max*D) sequential + (K_max,D) carry",
        "precomputed_step_cost": "one MXU precompute + D-free resolution",
        "proposed_total": int(np.asarray(rf.stats.proposed).sum()),
        "accepted_total": int(np.asarray(rf.stats.accepted).sum()),
    }
    # Deltas vs the previously tracked record (PR-2 baseline on the first
    # run after the §11 refactor: its fields were legacy_/precomputed_).
    if out_path is not None and os.path.exists(out_path):
        with open(out_path) as f:
            prev = json.load(f)
        prev_ref = prev.get("dp_reference_wall_s",
                            prev.get("legacy_wall_s"))
        prev_fast = prev.get("dp_precomputed_wall_s",
                             prev.get("precomputed_wall_s"))
        if prev_ref and prev_fast:
            record["baseline"] = {
                "dp_reference_wall_s": prev_ref,
                "dp_precomputed_wall_s": prev_fast,
                "dp_speedup": prev_ref / prev_fast,
                # The PR-2 record was mean-of-repeats; this bench switched
                # to min-of-repeats, so part of any delta is methodology.
                "timing": prev.get("timing", "mean_of_repeats"),
            }
            record["dp_precomputed_delta_vs_baseline"] = prev_fast / fast_s
    record["timing"] = "min_of_repeats"
    # Only persist when a path is given (the __main__ canonical run does);
    # suite/CI fast-mode invocations must not clobber the tracked record.
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)

    tag = f"n{n}_d{d}_k{k_max}_cap{cap}"
    rows += [
        (f"validator_dp_reference_{tag}", ref_s * 1e6,
         "per_step=O(K_max*D)"),
        (f"validator_dp_precomputed_{tag}", fast_s * 1e6,
         f"per_step=O(cap);speedup={ref_s / fast_s:.2f}x"),
        (f"validator_dp_serial_{tag}", serial_s * 1e6,
         "serial_scan;epoch1_validator_only"),
        (f"validator_dp_logdepth_{tag}", logd_s * 1e6,
         f"fixed_point;vs_serial={serial_s / logd_s:.2f}x"),
        (f"validator_dp_adaptive_{tag}", adapt_s * 1e6,
         f"cap={cap_ad};warm_speedup={full_warm_s / adapt_s:.2f}x"),
        (f"validator_bp_reference_{tag}", bp_ref_s * 1e6,
         "per_step=O(K_max*D) refit;epoch1_validator_only"),
        (f"validator_bp_gram_{tag}", bp_gram_s * 1e6,
         f"gram_carry;speedup={bp_ref_s / bp_gram_s:.2f}x"
         f";propose_epoch_us={bp_prop_s * 1e6:.0f}"),
        (f"validator_bp_pass_{tag}", bp_pass_gram_s * 1e6,
         f"whole_pass;vs_reference={bp_pass_ref_s / bp_pass_gram_s:.2f}x"),
    ]
    if not quiet:
        for r in rows:
            print(f"{r[0]},{r[1]:.0f},{r[2]}")
    return rows


if __name__ == "__main__":
    run(out_path=os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_validator.json"))
