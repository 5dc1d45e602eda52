#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: run its traffic at each
of a list of rates, one process, and print a line per rate.

    python3 chipbench/sweep.py --workload deep96.serve --seed 1 \
        --seconds 10 --rates 250,500,1000,2000

A rate is sustained when the 95th percentile stays under `--p95-ms` and
the backlog does not grow: the median latency of the last tenth of the
requests is within twice that of the first tenth.  The cell's mix is then
set, by hand, to about four fifths of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import common  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--p95-ms", type=float, default=20.0)
    args = ap.parse_args()
    common.use_compile_cache()
    import numpy as np
    import traffic

    spec = common.benchmark_spec()
    cell = common.find_cell(spec, args.workload)
    cfg = common.config_of(spec, cell)
    base = common.mix_of(cell)
    limits = common.load_json(
        os.path.join(HERE, "limits", cell["name"] + ".json"))["limits"]
    devs = common.require_devices(int(cell["chips"]))
    clock = common.CompileClock()
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(base, rate_per_s=rate)
        run = traffic.run_open_loop(cfg, mix, cell, args.seed, args.seconds,
                                    False, devs, clock, time.perf_counter(),
                                    limits)
        lat = np.asarray(run.counters["latencies_s"])
        tenth = max(1, lat.size // 10)
        head = float(np.median(lat[:tenth]))
        tail = float(np.median(lat[-tenth:]))
        p95 = run.end_to_end["serve_p95_ms"]
        ok = bool(p95 <= args.p95_ms and tail <= 2 * head
                  and all(c[3] for c in run.checks) and run.failed == 0)
        row = {"rate": rate, "p50_ms": 1e3 * float(np.median(lat)),
               "p95_ms": p95, "qps": run.end_to_end["serve_qps"],
               "late_p95_ms": run.counters["late_p95_ms"],
               "head_ms": 1e3 * head, "tail_ms": 1e3 * tail,
               "bucket_fill": run.counters["bucket_fill"],
               "compiles": run.counters["compiles"], "sustained": ok}
        rows.append(row)
        print(json.dumps(row), flush=True)
    best = max((r["rate"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"highest_sustained": best}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
