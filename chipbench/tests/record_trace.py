#!/usr/bin/env python3
"""Record the small chip trace the reducer's tests read
(`chipbench/tests/data/small.xplane.pb`).  Run on a TPU:

    python3 chipbench/tests/record_trace.py [--out DIR]

It traces, inside one `bench.window` span, a steady-state `partial_fit`
call of a tiny OCC DP-means job (8 epochs of 256 points at D=96) and one
score and one top-10 request through `ClusterService.submit`, and writes
the counts the test compares with beside the trace (`small.json`).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import common  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(HERE, "data"))
    args = ap.parse_args()
    common.use_compile_cache()
    import jax
    import numpy as np
    from repro.core import DPMeansTransaction, OCCEngine
    from repro.core.occ import CenterPool
    from repro.serving import ClusterService, Query, ServeConfig, SnapshotStore
    import traffic

    common.require_devices(1)
    _, x = common.mixture(5, 3 * 2048, 64, 96, 0.5)
    eng = OCCEngine(DPMeansTransaction(1.0, k_max=1024), pb=256,
                    validate_cap="adaptive")
    for lo in (0, 2048):
        jax.block_until_ready(eng.partial_fit(x[lo:lo + 2048]).assign)
    pool = eng.pool
    store = SnapshotStore()
    store.publish_pool(CenterPool(pool.centers, pool.mask, pool.count,
                                  pool.overflow))
    svc = ClusterService(store, ServeConfig())
    q = np.asarray(x[:8])
    for kind, k in (("score", 0), ("topk", 10)):       # compile first
        svc.submit(Query(q, kind=kind, k=k))
    tdir = common.trace_dir("record")
    jax.profiler.start_trace(tdir, profiler_options=traffic._trace_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.partial_fit"):
            res = eng.partial_fit(x[4096:6144])
        with jax.profiler.TraceAnnotation("bench.pull"):
            jax.block_until_ready(res.assign)
        for kind, k in (("score", 0), ("topk", 10)):
            with jax.profiler.TraceAnnotation("bench.submit"):
                svc.submit(Query(q, kind=kind, k=k))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(src[0], os.path.join(args.out, "small.xplane.pb"))
    meta = {"epochs": int(res.stats.accepted.shape[0]),
            "k_start": int(pool.count),
            "accepted": [int(a) for a in np.asarray(res.stats.accepted)],
            "pb": 256, "dim": 96, "serve_dispatches": 2, "serve_rows": 8,
            "device_kind": jax.devices()[0].device_kind}
    with open(os.path.join(args.out, "small.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta))
    return 0


if __name__ == "__main__":
    sys.exit(main())
