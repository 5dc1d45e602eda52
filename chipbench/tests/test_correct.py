"""`correct` at a size the CPU holds: the program passes, the control fails,
and each fault a cell can have, planted in the program underneath a whole
run of the harness, turns `correct` false.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests

The look for a chip is skipped (`devices=`); everything else is the run a
cell makes, with the cell's own limits (`chipbench/limits/`).  A training
cell's small size and its faults come from its algorithm's file
(`chipbench/algorithms/<algorithm>.py`: `small`, `FAULTS`).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import common  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

SPEC = common.benchmark_spec()
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 4321
TRAIN_CELLS = [c["name"] for c in SPEC["workloads"]
               if common.mix_of(c)["shape"] == "jobs" and c["chips"] == 1]
MESH_CELLS = [c["name"] for c in SPEC["workloads"]
              if common.mix_of(c)["shape"] == "jobs" and c["chips"] > 1]
SERVE_CELLS = [c["name"] for c in SPEC["workloads"]
               if common.mix_of(c)["shape"] == "open_loop"]


def algorithm_of(cell: str):
    return common.algorithm(common.config_of(SPEC,
                                             common.find_cell(SPEC, cell)))


def small_config(cell: str) -> dict:
    """The cell's configuration at the size its algorithm runs on the CPU
    (`small`); on several chips, 64 points a chip to an epoch."""
    c = common.find_cell(SPEC, cell)
    cfg = algorithm_of(cell).small(common.config_of(SPEC, c))
    if c["chips"] > 1:
        cfg["pb"] = 64 * c["chips"]
    return cfg


def small_mix(cell: str, **over) -> dict:
    mix = dict(common.mix_of(common.find_cell(SPEC, cell)))
    if mix["shape"] == "jobs":
        mix.update(chunk_points=2048, check_block=256)
    else:
        mix.update(rate_per_s=100, check_block=64, threads=8)
    mix.update(over)
    return mix


def devices(n):
    import jax
    return jax.devices()[:n]


def run_small(cell: str, seconds: float = 0.3, **mix_over) -> dict:
    return run.run_cell(SPEC, cell, SEED, seconds, False, devices=devices,
                        config=small_config(cell),
                        mix=small_mix(cell, **mix_over), peaks=PEAKS,
                        t_start=time.perf_counter())


def run_counted(cell: str, seed: int, seconds: float = 0.0, **over):
    """One small run of a training cell: its result line, and the counters
    the jobs generator handed the readers (K, epochs).  A window of 0 s
    holds one job.  `over` replaces `run_cell`'s keyword arguments."""
    kw = dict(devices=devices, config=small_config(cell),
              mix=small_mix(cell), peaks=PEAKS, t_start=time.perf_counter())
    kw.update(over)
    runs = []
    jobs = traffic.SHAPES["jobs"]

    def counted(*args):
        runs.append(jobs(*args))
        return runs[-1]

    traffic.SHAPES["jobs"] = counted
    try:
        out = run.run_cell(SPEC, cell, seed, seconds, False, **kw)
    finally:
        traffic.SHAPES["jobs"] = jobs
    return out, runs[0].counters


def limits_of(cell: str) -> dict:
    return common.load_json(
        os.path.join(BENCH, "limits", cell + ".json"))["limits"]


@pytest.mark.parametrize("cell", TRAIN_CELLS + SERVE_CELLS)
def test_program_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_control_fails(cell):
    import jax.numpy as jnp
    cfg, mix = small_config(cell), small_mix(cell)
    prog, ctrl = control.train_readings(cfg, mix, SEED, devices(1),
                                        jnp.bfloat16)
    lim = limits_of(cell)
    assert all(prog[k] <= lim[k] for k in prog), prog
    assert any(ctrl[k] > lim[k] for k in ctrl), ctrl


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_control_fails(cell):
    import jax.numpy as jnp
    c = common.find_cell(SPEC, cell)
    prog, ctrl = control.serve_readings(
        small_config(cell), small_mix(cell), c, SEED, 0.3, devices(1),
        limits_of(cell), jnp.bfloat16)
    lim = limits_of(cell)
    assert all(prog[k] <= lim[k] for k in prog), prog
    assert any(ctrl[k] > lim[k] for k in ctrl), ctrl


# ------------------------------------------------------ faults, training

@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in TRAIN_CELLS
    for fault in algorithm_of(cell).FAULTS])
def test_train_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(cell)
    assert not out["correct"], out["checks"]


# ------------------------------------------------------- faults, serving

def _half_group(monkeypatch):
    """Half of each dispatched group's rows left out: they are answered
    from a zero row."""
    from repro.serving.cluster_service import ClusterService
    orig = ClusterService._run_step

    def run_step(self, snap, xp, n, kind, k):
        keep = (n + 1) // 2
        xp = xp.at[keep:].set(0.0)
        return orig(self, snap, xp, n, kind, k)

    monkeypatch.setattr(ClusterService, "_run_step", run_step)


def _altered_label(monkeypatch):
    """Every tenth response's first label changed where the service returns
    it (the warm-up's requests go through here too)."""
    from repro.serving.cluster_service import ClusterService
    orig = ClusterService.submit
    seen = []

    def submit(self, query):
        resp = orig(self, query)
        seen.append(1)
        if len(seen) % 10 == 0:
            labels = resp.labels.copy()
            labels.flat[0] = (labels.flat[0] + 1) % self.store.latest().count
            resp = resp._replace(labels=labels)
        return resp

    monkeypatch.setattr(ClusterService, "submit", submit)


@pytest.mark.parametrize("fault", [_half_group, _altered_label])
@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(cell, rows={"4": 1.0})
    assert not out["correct"], out["checks"]


# -------------------------------------------------- faults, several chips

def _mesh_run(*args) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.join(HERE, "mesh_case.py"),
                          *args], env=env, cwd=HERE, capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_mesh_program_is_correct(cell):
    out = _mesh_run()
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_mesh_without_exchange_is_not_correct(cell):
    out = _mesh_run("no_exchange")
    assert not out["correct"], out["checks"]
