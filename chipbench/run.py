#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  Everything a cell is comes from data: its
entry in BENCHMARK.json, its configuration (`chipbench/configs/`), its
traffic mix (`chipbench/mixes/`), the limits `correct` is held to
(`chipbench/limits/<cell>.json`) and one reader per per-layer metric
(`chipbench/layers/<metric>.py`).  One process: it finds the chips (and
exits non-zero, printing no result, where JAX finds no TPU or too few),
makes its data on the device from the seed, warms up, measures for
`--seconds`, checks the answers against the plain reference
(`chipbench/reference.py`) and prints one JSON line as the last line of
standard output.  With `--trace 1` it traces part of the window with the
JAX profiler and prints the per-layer metrics in place of the end-to-end
ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import common  # noqa: E402


def layer_reader(name: str):
    path = os.path.join(HERE, "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(entries: list, cell: dict, reported: set | None = None):
    """The entries this cell reports: those that list it, and those without
    a `workloads` key (for a per-layer metric: where its `moves` is
    reported)."""
    out = []
    for m in entries:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif reported is None or m["moves"] in reported:
            out.append(m)
    return out


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             *, devices=common.require_devices,
             config=None, mix=None, limits=None, peaks=None,
             t_start=T_START) -> dict:
    """One run of one cell.  Returns the result line as a dict.  `devices`
    and the overrides exist for the tests, which drive a run on the CPU at
    a small size, some with the timed path broken underneath."""
    cell = common.find_cell(spec, name)
    cfg = config if config is not None else common.config_of(spec, cell)
    mix = mix if mix is not None else common.mix_of(cell)
    if limits is None:
        limits = common.load_json(
            os.path.join(HERE, "limits", name + ".json"))["limits"]
    devs = devices(int(cell["chips"]))
    if peaks is None:
        peaks = common.peaks_for(devs[0].device_kind)
    clock = common.CompileClock()
    import traffic
    run = traffic.SHAPES[mix["shape"]](cfg, mix, cell, seed, seconds, trace,
                                        devs, clock, t_start, limits)
    common.log("set-up: " + ", ".join(
        f"{k} {v:.4f}" for k, v in run.setup_parts.items())
        + f", setup_s {run.end_to_end['setup_s']:.4f}")
    e2e = metrics_of(spec["end_to_end"], cell)
    device = common.device_info(devs, run.counters["memory_peak_bytes"])
    line = {}
    if not trace:
        metrics = {m["name"]: common.metric(run.end_to_end[m["name"]],
                                            m["unit"]) for m in e2e}
    else:
        import tracing
        red = tracing.reduce_dir(run.traced, len(devs))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        line["breakdown"] = red.breakdown()
        ctx = {"cell": cell, "config": cfg, "mix": mix, "peaks": peaks,
               "counters": run.counters, "trace": red}
        metrics = {}
        for m in metrics_of(spec["per_layer"], cell,
                            {m["name"] for m in e2e}):
            value = layer_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = common.metric(value, m["unit"])
        for k, v in red.summary().items():
            common.log(f"trace: {k} {v}")
    correct = all(ok for *_, ok in run.checks) and run.failed == 0
    for k, v in sorted(metrics.items()):
        common.log(f"metric: {k} {v['value']!r} {v['unit']}")
    checks = {n: {"value": v, "limit": lim} for n, v, lim, _ in run.checks}
    for n, v, lim, ok in run.checks:
        common.log(f"check: {n} {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}")
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    out.update(line)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    common.use_compile_cache()
    spec = common.benchmark_spec()
    try:
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except common.NoChip as e:
        common.log(f"no result: {e}")
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
