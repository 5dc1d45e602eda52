"""Observability walkthrough: one registry + one trace for a whole run.

Three acts (§15 of DESIGN.md):

  1. a single shared `Obs` threaded through engine → store → WAL: every
     layer's counters land in ONE registry, read back via `dump()` /
     Prometheus-style `exposition()` — the same text the HA coordinator
     serves over its CTRL channel;
  2. the same run traced: spans and instants from every subsystem land
     in one Chrome-trace JSON — open it at https://ui.perfetto.dev;
  3. the flagship: a 3-node HA cluster with the master SIGKILLed
     mid-pass, `trace_out` merging every process's timeline (the victim
     flushes its trace before `os._exit`) into one file whose span
     categories cover engine, transport, WAL, fault, and the HA control
     plane.  (Act 3 spawns processes; pass --ha to include it.)

  PYTHONPATH=src python examples/observability.py [--ha]
"""
import os
import sys
import tempfile

import jax.numpy as jnp

from repro.checkpoint import DeltaWAL
from repro.core import DPMeansTransaction, OCCEngine
from repro.data import dp_stick_breaking_data
from repro.obs import Obs, Tracer, load_trace, trace_categories, \
    validate_trace
from repro.serving.snapshot import SnapshotStore


def main():
    x = jnp.asarray(dp_stick_breaking_data(2048, seed=0, dim=8)[0])
    lam, k_max, pb = 4.0, 128, 128
    out_dir = tempfile.mkdtemp(prefix="occ-obs-")
    trace_path = os.path.join(out_dir, "trace.json")

    # --- acts 1+2: one Obs, every layer, one registry + one trace --------
    # Components create a private Obs() when none is given (counters still
    # work standalone); passing ONE bundle is what unifies the run.
    obs = Obs(tracer=Tracer("observability-demo"), trace_path=trace_path)
    wal = DeltaWAL(os.path.join(out_dir, "wal"), model="demo",
                   checkpoint_every=4, obs=obs)
    store = SnapshotStore(capacity=16, delta=True, model="demo", wire=wal)
    engine = OCCEngine(DPMeansTransaction(lam, k_max=k_max), pb=pb,
                       publish=store.publish_pass, obs=obs)
    for lo in range(0, 2048, 512):
        engine.partial_fit(x[lo:lo + 512])
    engine.flush()
    wal.close()
    obs.flush()

    print("--- registry (Prometheus text exposition, excerpt) ---")
    for line in obs.metrics.exposition().splitlines():
        if line.startswith(("engine_p", "engine_accepted", "wal_appends",
                            "wal_checkpoints", "engine_pass_s_")):
            print(f"  {line}")
    for width in ("full", "capped"):    # the pass's validator width
        h = obs.metrics.get_histogram("engine_pass_s", width=width)
        if h is not None:
            print(f"engine passes at {width} width: {h.count}, pass p50 "
                  f"{h.percentile(50) * 1e3:.1f}ms")
    print(f"K={int(engine.pool.count)}, "
          f"conflict_rate={obs.metrics.value('engine_conflict_rate'):.3f}")

    trace = load_trace(trace_path)
    assert validate_trace(trace) == []
    print(f"trace: {len(trace['traceEvents'])} events, categories "
          f"{sorted(trace_categories(trace))}\n"
          f"  -> open {trace_path} at https://ui.perfetto.dev")

    # --- act 3 (--ha): the merged multi-process chaos timeline -----------
    if "--ha" in sys.argv[1:]:
        from repro.launch.ha_cluster import HAConfig, run_ha_cluster
        ha_trace = os.path.join(out_dir, "trace_ha.json")
        rec = run_ha_cluster(HAConfig(
            n=1024, dim=8, pb=64, k_max=128, lam=3.0, n_workers=2,
            n_nodes=3, kill_master_after_version=6, trace_out=ha_trace,
            quiet=True))
        merged = load_trace(ha_trace)
        assert validate_trace(merged) == []
        pids = {e["pid"] for e in merged["traceEvents"]}
        print(f"HA chaos: {rec['promotions']} promotion, "
              f"{len(merged['traceEvents'])} events from {len(pids)} "
              f"processes (killed master included), categories "
              f"{sorted(trace_categories(merged))}\n"
              f"  -> open {ha_trace} at https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
