"""`correct` at a size the CPU holds: the program passes, the control fails,
and each fault a cell can have, planted in the program underneath a whole
run of the harness, turns `correct` false.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests

The look for a chip is skipped (`devices=`); everything else is the run a
cell makes, with the cell's own limits (`chipbench/limits/`).
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

SPEC = common.benchmark_spec()
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 4321
TRAIN_CELLS = [c["name"] for c in SPEC["workloads"]
               if common.mix_of(c)["shape"] == "jobs" and c["chips"] == 1]
MESH_CELLS = [c["name"] for c in SPEC["workloads"]
              if common.mix_of(c)["shape"] == "jobs" and c["chips"] > 1]
SERVE_CELLS = [c["name"] for c in SPEC["workloads"]
               if common.mix_of(c)["shape"] == "open_loop"]


def small_config(cell: str) -> dict:
    """The cell's configuration at a size the CPU runs in seconds: its
    width, fewer components, points and slots, and λ = 1 with more noise,
    so that components hold several centers and some decisions fall near
    λ, as they do at full size."""
    cfg = dict(common.config_of(SPEC, common.find_cell(SPEC, cell)))
    cfg.update(n_components=256, n_points=16384, k_max=4096, noise=0.8,
               lam=1.0,
               pb=min(cfg["pb"], 256))
    if common.find_cell(SPEC, cell)["chips"] > 1:
        cfg["pb"] = 64 * common.find_cell(SPEC, cell)["chips"]
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

def _stale_state(monkeypatch):
    """A pass that returns the pool it was given: no center is ever kept."""
    from repro.core.engine import OCCEngine
    orig = OCCEngine._commit_stream_pass

    def commit(self, xb, state):
        before = self._pool
        if before is None:
            before = self.txn.init_pool(xb[:self.pb])
        res = orig(self, xb, state)
        self._pool = before
        return res._replace(pool=before)

    monkeypatch.setattr(OCCEngine, "_commit_stream_pass", commit)


def _half_batch(monkeypatch):
    """Half of each call's points left out; their answers copied from the
    half that ran."""
    import jax.numpy as jnp
    from repro.core.engine import OCCEngine
    orig = OCCEngine.partial_fit

    def partial_fit(self, xb, **kw):
        res = orig(self, xb[:xb.shape[0] // 2], **kw)
        return res._replace(assign=jnp.concatenate([res.assign] * 2),
                            send=jnp.concatenate([res.send] * 2))

    monkeypatch.setattr(OCCEngine, "partial_fit", partial_fit)


def _altered_answer(monkeypatch):
    """One point's assignment changed where the pass produces it."""
    from repro.core.engine import OCCEngine
    orig = OCCEngine.partial_fit

    def partial_fit(self, xb, **kw):
        res = orig(self, xb, **kw)
        k = int(self.pool.count)
        a = res.assign.at[7].set((res.assign[7] + 1) % k)
        return res._replace(assign=a)

    monkeypatch.setattr(OCCEngine, "partial_fit", partial_fit)


@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _altered_answer])
@pytest.mark.parametrize("cell", TRAIN_CELLS)
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
