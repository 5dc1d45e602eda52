"""Benchmark regression gate — fails CI on real slowdowns in key metrics.

Measures the latency-critical paths at --quick sizes:

  * ``validator_pass_us`` — one warm compiled OCC pass (bootstrap + epoch
    scan + the §11 precomputed validator: the training hot path);
  * ``service_p99_ms`` / ``service_p50_ms`` — solo request latency through
    `ClusterService.score` with warm jit caches (the serving hot path);
  * ``serve_topk_us`` — warm `ClusterService.topk` microbatch latency (the
    §16 retrieval-serving hot path: streaming top-k dispatch);
  * ``serve_qos_p99_us`` — interactive p99 through the coalescing admission
    queue while an analytics scan sits parked in its own lane (the §17
    mixed-traffic hot path: priority lanes must keep the interactive
    deadline timer independent of the parked scan);
  * ``transport_commit_us`` — median publish→all-followers-acked latency
    over loopback sockets (the §13 replication barrier hot path);
  * ``recovery_replay_us`` — full `recover_wal` wall time (checkpoint
    restore + delta replay: the §14 crash-recovery MTTR path).

Raw wall times are machine-dependent, so the GATE compares *normalized*
metrics: each raw time divided by ``reference_us``, a warm jitted matmul
timed on the same machine in the same process.  A slower CI runner scales
metric and reference together and the ratio holds; a code regression (or
the built-in ``--inject-sleep-ms`` self-test) inflates only the metric and
trips the gate.  Timings take the MIN over trials (robust to scheduler
noise; p99 is a per-trial tail, then min over trials).

The committed baseline lives in ``benchmarks/baselines/
BENCH_regress_quick.json`` (regenerate with ``--update`` after an
intentional perf change).  Exit status: 0 clean, 1 on >``--tol`` (default
30%) normalized slowdown in any key metric.  With ``--history-dir``
pointing at prior green-run ``--out`` artifacts, each metric's tolerance
tightens from the blanket 30% down toward its OBSERVED run-to-run spread
(median/MAD over the rolling window — see `rolling_tolerance`), so a CI
that accumulates artifacts gets a progressively sharper gate for free.

  PYTHONPATH=src python -m benchmarks.check_regress            # gate
  PYTHONPATH=src python -m benchmarks.check_regress --update   # rebaseline
  PYTHONPATH=src python -m benchmarks.check_regress --inject-sleep-ms 2
  # ^ self-test: the injected sleep must make the gate FAIL (exit 1)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

KEY_METRICS = ("validator_pass_us", "service_p99_ms", "serve_topk_us",
               "serve_qos_p99_us", "transport_commit_us",
               "recovery_replay_us")
BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baselines", "BENCH_regress_quick.json")
SIZES = dict(n=1024, dim=16, pb=64, k_max=256, lam=4.0,
             n_requests=200, request=17, trials=7,
             qos_requests=40, qos_trials=3, qos_deadline_ms=3.0,
             repl_followers=2, repl_versions=16, repl_trials=3,
             wal_versions=30, wal_dk=4, wal_ckpt_every=8, wal_trials=3)


def _reference_us(obs, trials: int = 7, reps: int = 50) -> float:
    """Warm jitted matmul on this machine: the speed normalizer (timed
    through the registry like every other metric here)."""
    a = jnp.asarray(np.random.default_rng(0).normal(
        size=(512, 512)).astype(np.float32))
    f = jax.jit(lambda a: a @ a)
    f(a).block_until_ready()
    for _ in range(trials):
        with obs.metrics.timer("bench_reference_s"):
            for _ in range(reps):
                f(a).block_until_ready()
    return obs.metrics.get_histogram("bench_reference_s").min / reps * 1e6


def _hist_summary(obs, name: str, **labels) -> dict | None:
    h = obs.metrics.get_histogram(name, **labels)
    if h is None or not h.count:
        return None
    return dict(count=h.count, p50=float(h.percentile(50)),
                p99=float(h.percentile(99)))


def measure(inject_sleep_ms: float = 0.0) -> dict:
    """Every number below is read back from ONE shared `MetricsRegistry`:
    the gate's own timers (``bench_*_s`` histograms; sleep injection lands
    INSIDE the timed blocks, so the self-test exercises the registry
    measurement path) plus the components' internal histograms
    (engine_pass_s, serve_request_s, transport_ack_rtt_s, wal_*_s), which
    ride along in the artifact as `component_metrics`."""
    from repro.core import DPMeansTransaction, OCCEngine
    from repro.data import dp_stick_breaking_data
    from repro.obs import Obs
    from repro.serving import ClusterService, SnapshotStore

    s = SIZES
    obs = Obs()
    m = obs.metrics
    x, _, _ = dp_stick_breaking_data(s["n"], seed=0, dim=s["dim"])
    x = jnp.asarray(x)
    inject = inject_sleep_ms / 1e3

    # --- validator pass: one compiled pass, warm ------------------------
    eng = OCCEngine(DPMeansTransaction(s["lam"], k_max=s["k_max"]),
                    pb=s["pb"], obs=obs)
    eng.run(x).pool.count.block_until_ready()        # compile + warm
    for _ in range(s["trials"]):
        with m.timer("bench_validator_pass_s"):
            eng.run(x).pool.count.block_until_ready()
            if inject:
                time.sleep(inject)   # --inject-sleep-ms self-test hook
    validator_pass_us = m.get_histogram("bench_validator_pass_s").min * 1e6

    # --- service latency: warm solo requests ----------------------------
    store = SnapshotStore()
    eng2 = OCCEngine(DPMeansTransaction(s["lam"], k_max=s["k_max"]),
                     pb=s["pb"], publish=store.publish_pass)
    eng2.partial_fit(x)
    eng2.flush()
    svc = ClusterService(store, obs=obs)
    q = x[:s["request"]]
    svc.score(q)                                     # warm (bucket, cap)
    p50s, p99s = [], []
    for t in range(s["trials"]):
        for _ in range(s["n_requests"]):
            with m.timer("bench_service_request_s", trial=t):
                svc.score(q)
                if inject:
                    time.sleep(inject)
        h = m.get_histogram("bench_service_request_s", trial=t)
        p50s.append(h.percentile(50))    # n_requests < sample_limit:
        p99s.append(h.percentile(99))    # exact, numpy-compatible

    # --- top-k serving: warm streaming-topk microbatch (§16) -------------
    svc.topk(q, k=8)                                 # warm (bucket, cap, k)
    for _ in range(s["trials"]):
        with m.timer("bench_serve_topk_s"):
            for _ in range(20):
                svc.topk(q, k=8)
                if inject:
                    time.sleep(inject)   # inside the timed block
    serve_topk_us = m.get_histogram("bench_serve_topk_s").min / 20 * 1e6

    # --- QoS mixed traffic: interactive p99 behind a parked scan (§17) ---
    import threading
    from repro.serving import Query, ServeConfig
    qsvc = ClusterService(
        store, ServeConfig(coalesce=True, coalesce_bucket=64,
                           coalesce_delay_ms=s["qos_deadline_ms"]), obs=obs)
    qi = q[:5]
    qsvc.score(qi)                   # warm the coalesced dispatch shapes
    qsvc.topk(q, k=8)
    park = threading.Thread(target=lambda: qsvc.submit(
        Query(q, kind="topk", k=8, priority="analytics",
              deadline_ms=120_000.0, max_staleness=2)))
    park.start()
    while qsvc.queue_depth_rows() < s["request"]:
        pass                         # the scan is parked in its own lane
    qp99s = []
    for t in range(s["qos_trials"]):
        for _ in range(s["qos_requests"]):
            with m.timer("bench_serve_qos_s", trial=t):
                qsvc.score(qi)
                if inject:
                    time.sleep(inject)
        qp99s.append(m.get_histogram("bench_serve_qos_s",
                                     trial=t).percentile(99))
    serve_qos_p99_us = min(qp99s) * 1e6
    qsvc.close()                     # flushes the parked scan (never drops)
    park.join(timeout=10)

    # --- replication commit: publish → all followers acked ---------------
    from benchmarks.transport import measure_commit
    transport_commit_us = min(
        measure_commit(s["repl_followers"], s["repl_versions"], dk=4,
                       dim=s["dim"], inject_sleep_s=inject,
                       obs=obs, trial=t)["commit_p50_us"]
        for t in range(s["repl_trials"]))

    # --- crash recovery: checkpoint restore + WAL delta replay -----------
    from benchmarks.recovery import measure_recovery
    recovery_replay_us = min(
        measure_recovery(s["wal_versions"], s["wal_dk"], s["dim"],
                         s["wal_ckpt_every"], inject_sleep_s=inject,
                         obs=obs, trial=t)["recovery_replay_us"]
        for t in range(s["wal_trials"]))

    ref_us = _reference_us(obs)
    metrics = {
        "validator_pass_us": validator_pass_us,
        "service_p50_ms": float(min(p50s) * 1e3),
        "service_p99_ms": float(min(p99s) * 1e3),
        "serve_topk_us": serve_topk_us,
        "serve_qos_p99_us": serve_qos_p99_us,
        "transport_commit_us": transport_commit_us,
        "recovery_replay_us": recovery_replay_us,
    }
    return {
        "bench": "regress_quick",
        "sizes": dict(s),
        "reference_us": ref_us,
        "metrics": metrics,
        "normalized": {k: v / ref_us for k, v in metrics.items()},
        # supplementary: what the instrumented components measured about
        # themselves during the same run (same registry, free to export)
        "component_metrics": {
            "engine_pass_s": {w: _hist_summary(obs, "engine_pass_s", width=w)
                              for w in ("full", "capped")},
            "serve_request_s": _hist_summary(obs, "serve_request_s",
                                             model=""),
            "serve_queue_wait_s": _hist_summary(obs, "serve_queue_wait_s",
                                                model=""),
            "transport_ack_rtt_s": _hist_summary(obs, "transport_ack_rtt_s"),
            "wal_append_s": _hist_summary(obs, "wal_append_s"),
            "wal_recover_s": _hist_summary(obs, "wal_recover_s"),
        },
    }


def rolling_tolerance(history: list[float], base: float, default_tol: float,
                      floor: float = 0.10, min_points: int = 3,
                      k: float = 5.0) -> float:
    """Per-metric gate tolerance from a rolling window of prior HEALTHY
    normalized measurements (pure; unit-tested in
    tests/test_check_regress.py).

    The default 30% tolerance is sized for one cold CI runner with no
    memory; with a history of green-run artifacts the metric's real run-
    to-run spread is known, and the gate can afford to be tighter.  Spread
    is estimated robustly — median/MAD over the history-to-baseline ratios
    (MAD scaled by 1.4826 ≈ sigma for a normal), so one noisy historical
    run widens nothing — then:

        tol = clamp(|median - 1| + k * sigma, floor, default_tol)

    The |median - 1| term keeps a systematic baseline/runner offset from
    eating the noise allowance.  Fewer than `min_points` samples: the
    default applies unchanged (no history, no claims)."""
    if base <= 0 or len(history) < min_points:
        return default_tol
    ratios = sorted(h / base for h in history)
    med = ratios[len(ratios) // 2]
    mad = sorted(abs(r - med) for r in ratios)[len(ratios) // 2]
    spread = abs(med - 1.0) + k * 1.4826 * mad
    return min(default_tol, max(floor, spread))


def load_history(history_dir: str) -> dict[str, list[float]]:
    """Normalized key metrics from every parseable BENCH*.json artifact in
    `history_dir` (prior green runs' --out files).  Torn or foreign files
    are skipped — a corrupt artifact must not widen or crash the gate."""
    out: dict[str, list[float]] = {k: [] for k in KEY_METRICS}
    if not os.path.isdir(history_dir):
        return out
    for fn in sorted(os.listdir(history_dir)):
        if not (fn.startswith("BENCH") and fn.endswith(".json")):
            continue
        try:
            with open(os.path.join(history_dir, fn)) as f:
                rec = json.load(f)
            if rec.get("bench") != "regress_quick":
                continue
            norm = rec["normalized"]
            for key in KEY_METRICS:
                if key in norm:
                    out[key].append(float(norm[key]))
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return out


def check(baseline: dict, fresh: dict, tol: float,
          history: dict[str, list[float]] | None = None) -> list[str]:
    failures = []
    for key in KEY_METRICS:
        base = baseline["normalized"].get(key)
        if base is None:        # metric newer than the committed baseline
            print(f"{key}: no baseline entry — skipped (rebaseline with "
                  f"--update)")
            continue
        key_tol = rolling_tolerance(history.get(key, ()) if history else [],
                                    base, tol)
        now = fresh["normalized"][key]
        ratio = now / base
        verdict = "FAIL" if ratio > 1.0 + key_tol else "ok"
        tightened = (f", tol={100 * key_tol:.0f}% from "
                     f"{len(history[key])}-run history"
                     if history and key_tol < tol else "")
        print(f"{key}: baseline_norm={base:.3f} fresh_norm={now:.3f} "
              f"ratio={ratio:.2f} (raw {fresh['metrics'][key]:.0f} vs "
              f"{baseline['metrics'][key]:.0f}) [{verdict}{tightened}]")
        if ratio > 1.0 + key_tol:
            failures.append(
                f"{key} regressed {100 * (ratio - 1):.0f}% "
                f"(> {100 * key_tol:.0f}% tolerance)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--tol", type=float,
                    default=float(os.environ.get("CHECK_REGRESS_TOL", 0.30)))
    ap.add_argument("--update", action="store_true",
                    help="write the fresh measurement as the new baseline")
    ap.add_argument("--inject-sleep-ms", type=float, default=0.0,
                    help="inject an artificial slowdown into the measured "
                         "paths — the gate must then FAIL (self-test)")
    ap.add_argument("--history-dir", default=None,
                    help="directory of prior green-run --out artifacts; "
                         "with >=3 of them the per-metric tolerance "
                         "tightens to the observed run-to-run spread")
    ap.add_argument("--out", default=None,
                    help="also write the fresh measurement here (artifact)")
    args = ap.parse_args(argv)

    fresh = measure(args.inject_sleep_ms)
    print(f"reference_us={fresh['reference_us']:.1f}  "
          f"(machine-speed normalizer)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(fresh, f, indent=2)
    if args.update:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(fresh, f, indent=2)
        print(f"baseline updated: {args.baseline}")
        return 0
    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; run with --update first",
              file=sys.stderr)
        return 2
    with open(args.baseline) as f:
        baseline = json.load(f)
    history = (load_history(args.history_dir)
               if args.history_dir else None)
    failures = check(baseline, fresh, args.tol, history)
    if failures:
        for msg in failures:
            print(f"REGRESSION: {msg}", file=sys.stderr)
        return 1
    print("regression gate: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
