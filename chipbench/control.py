#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip, at a
cell's own size: the program's, and the control's.

    python3 chipbench/control.py --workload deep96.train --seeds 1,2,3

The control is the reference put in the program's place and computed one
step below the configuration's float32: in one bfloat16 pass.  For a
training cell the configuration's algorithm file says what it recomputes
(`control()`; DP-means: every point's assignment and send decision, against
the pool the program built); for a serving cell it answers every request of
the cell's traffic in bfloat16.  Both are judged by the same float32
reference the benchmark's runs use (the algorithm's `check()`;
`reference.py`).  One process runs every seed; the program's training
readings need no measured window (one job per seed), its serving readings
a short window at the cell's own load.  Prints one JSON line per seed and a
last line with the largest program reading and the smallest control
reading of each number.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import common  # noqa: E402


def train_readings(cfg, mix, seed, devs, dtype):
    import jax.numpy as jnp
    import traffic

    algo = common.algorithm(cfg)
    x = traffic.job_data(cfg, mix, seed)
    (assigns, sends, pool), _ = traffic._one_job(
        x, cfg, dict(mix, _cell="control"), traffic._mesh(devs, mix),
        algo.transaction(cfg, seed))
    answer = (jnp.concatenate(assigns), jnp.concatenate(sends), pool)
    return (algo.check(x, answer, cfg, mix),
            algo.control(x, answer, cfg, mix, dtype))


def serve_readings(cfg, mix, cell, seed, seconds, devs, limits, dtype):
    import jax.numpy as jnp
    import numpy as np
    import reference
    import traffic

    run = traffic.run_open_loop(cfg, mix, cell, seed, seconds, False, devs,
                                common.CompileClock(), time.perf_counter(),
                                limits)
    prog = {n: v for n, v, _, _ in run.checks}
    means, pool = traffic._index(seed, cfg)
    due, kinds, rows = traffic.schedule(seed, mix, seconds)
    q = np.asarray(traffic.query_rows(seed, means, int(rows.sum()), cfg, mix))
    offs = np.concatenate([[0], np.cumsum(rows)])
    block = int(mix.get("check_block", 1024))
    ctrl = {"answers_bad": 0, "rank_gap": -math.inf, "score_err": -math.inf}
    for kind in mix["kinds"]:
        kk = int(mix["k"]) if kind == "topk" else 1
        sel = np.concatenate([np.arange(offs[i], offs[i + 1])
                              for i in range(len(due)) if kinds[i] == kind])
        pad = (-len(sel)) % block
        qk = jnp.asarray(np.concatenate(
            [q[sel], np.zeros((pad, q.shape[1]), np.float32)]))
        d, i = reference.topk_ref(qk, pool.centers, pool.count, k=kk,
                                  block=block, dtype=dtype)
        i = jnp.where(jnp.arange(qk.shape[0])[:, None] < len(sel), i, -2)
        b, g, e = reference.answer_gaps(qk, pool.centers, pool.count, i, d,
                                        block=block)
        ctrl["answers_bad"] += int(b)
        ctrl["rank_gap"] = max(ctrl["rank_gap"], float(g))
        ctrl["score_err"] = max(ctrl["score_err"], float(e))
    return prog, ctrl


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="serving cells: the short window at the cell's load")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    common.use_compile_cache()
    import jax.numpy as jnp

    spec = common.benchmark_spec()
    cell = common.find_cell(spec, args.workload)
    cfg = common.config_of(spec, cell)
    mix = common.mix_of(cell)
    limits = common.load_json(
        os.path.join(HERE, "limits", cell["name"] + ".json"))["limits"]
    devs = common.require_devices(int(cell["chips"]))
    dtype = getattr(jnp, args.dtype)
    worst_prog, least_ctrl = {}, {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        if mix["shape"] == "jobs":
            prog, ctrl = train_readings(cfg, mix, seed, devs, dtype)
        else:
            prog, ctrl = serve_readings(cfg, mix, cell, seed, args.seconds,
                                        devs, limits, dtype)
        for k, v in prog.items():
            worst_prog[k] = max(worst_prog.get(k, -math.inf), v)
        for k, v in ctrl.items():
            least_ctrl[k] = min(least_ctrl.get(k, math.inf), v)
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"program_max": worst_prog,
                      "control_min": least_ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
