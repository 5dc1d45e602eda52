"""What the OCC engine tells a profiler: the `occ.*` named scopes in the
compiled pass, the `engine.*` host spans around a `partial_fit`, and the
pass-time histogram labelled by validator width.  None of it may change a
result."""
from __future__ import annotations

import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DPMeansTransaction, OCCEngine, OFLTransaction
from repro.core.engine import _engine_pass_jit, _join_state
from repro.core.ofl import _draw_uniforms
from repro.core.occ import make_pool
from repro.obs import Obs, Tracer, span, validate_trace
from repro.serving import SnapshotStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("occ.pass", "occ.propose", "occ.compact", "occ.precompute",
          "occ.scan", "occ.commit")
HOST_SPANS = ("engine.dispatch", "engine.stats_wait", "engine.publish")


def _stream():
    """A quiet prefix, then a burst that overflows the shrunken validator
    window: cold full-width pass, two capped passes, one retried pass."""
    rng = np.random.default_rng(11)
    quiet = rng.normal(size=(384, 4)).astype(np.float32) * 0.1
    burst = rng.normal(size=(128, 4)).astype(np.float32) * 50.0
    return jnp.asarray(np.concatenate([quiet, burst]))


def _engine(obs=None, publish=None):
    return OCCEngine(DPMeansTransaction(2.0, 256), pb=64,
                     validate_cap="adaptive", publish=publish, obs=obs)


def _fit(eng, x, chunk=128):
    return [eng.partial_fit(x[lo:lo + chunk]) for lo in
            range(0, x.shape[0], chunk)]


# The loop of each payload resolution, as its op names show it: the
# fixed point's rounds, and the serial scan's steps in the pool-overflow
# branch of the fixed point's `conditional`.
RESOLUTION_LOOPS = {"logdepth": r"/occ\.scan/while/body/",
                    "serial": r"/occ\.scan/cond/[^/]+/while/body/"}


@pytest.mark.parametrize("resolution", ["serial", "logdepth"])
def test_compiled_pass_carries_every_occ_scope(resolution):
    """Every step of the pass is tagged, and the stage scopes nest inside
    `occ.pass` through the epoch loop, the loop of each payload resolution
    under `occ.scan`."""
    txn = DPMeansTransaction(1.0, k_max=64)
    lowered = _engine_pass_jit.lower(
        txn, make_pool(64, 4), jnp.zeros((256, 4)), (), pb=64,
        cap_warm=None, cap_rest=8, n_warm=1, n_bootstrap=0, mesh=None,
        data_axis="data")
    text = lowered.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    for scope in SCOPES[1:]:
        assert any(n.startswith("jit(_engine_pass)/occ.pass/")
                   and f"/{scope}/" in n for n in names), scope
    assert any(n.startswith("jit(_engine_pass)/occ.pass/")
               and re.search(RESOLUTION_LOOPS[resolution], n)
               for n in names), resolution


def test_sharded_pass_carries_every_occ_scope():
    """The same scopes on a four-device mesh, the propose under
    `shard_map`."""
    script = f"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import DPMeansTransaction
from repro.core.engine import _engine_pass_jit
from repro.core.occ import make_pool
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
text = _engine_pass_jit.lower(
    DPMeansTransaction(1.0, k_max=64), make_pool(64, 4),
    jnp.zeros((256, 4)), (), pb=64, cap_warm=None, cap_rest=8, n_warm=1,
    n_bootstrap=0, mesh=mesh, data_axis="data").as_text(debug_info=True)
print(jax.device_count(), [s in text for s in {SCOPES!r}])
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split("\n")[-2] == f"4 {[True] * len(SCOPES)}"


def test_partial_fit_host_spans_in_a_profiler_trace(tmp_path):
    """Without an `Obs`, the engine's spans land in a JAX profiler trace:
    each committing `partial_fit` holds its dispatch, the host's wait on
    the stats and the publish."""
    store = SnapshotStore()
    eng = _engine(publish=store.publish_pass)
    x = _stream()
    _fit(eng, x[:128])                          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _fit(eng, x[128:])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for p in jax.profiler.ProfileData.from_file(path).planes
             for line in p.lines for e in line.events
             if e.name.startswith("engine.")]
    calls = [(s, e) for s, e, n in spans if n == "engine.partial_fit"]
    assert len(calls) == 3
    for s0, e0 in calls:
        inside = {n for s, e, n in spans if s0 <= s and e <= e0}
        assert set(HOST_SPANS) <= inside, inside
    retries = [(s, e) for s, e, n in spans if n == "engine.retry"]
    assert len(retries) == eng.n_cap_retries == 1


def _op_names(compiled_text, params):
    """The named ops of a compiled program, its parameters left out."""
    return [n for n in re.findall(r'op_name="([^"]*)"', compiled_text)
            if n not in params]


def test_ofl_state_draw_is_one_program_under_its_scope():
    """A call's uniforms are one compiled program, and the carry's state
    joined ahead of them another: every op of both lies under
    `occ.state`."""
    text = _draw_uniforms.lower(jax.random.key(3), 0,
                                n=512).compile().as_text()
    names = _op_names(text, ("key", "offset"))
    assert names and all(n.startswith("jit(_draw_uniforms)/occ.state/")
                         for n in names), names
    text = _join_state.lower(jnp.zeros(3), jnp.zeros(5)).compile().as_text()
    names = _op_names(text, ("carried", "state"))
    assert names and all(n.startswith("jit(_join_state)/occ.state/")
                         for n in names), names


def _profiled_spans(tmp_path, fit):
    """(start, end, name) of every `engine.*` host span `fit()` makes under
    the JAX profiler."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fit()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in jax.profiler.ProfileData.from_file(path).planes
            for line in p.lines for e in line.events
            if e.name.startswith("engine.")]


def test_ofl_partial_fit_draws_its_state_under_engine_state(tmp_path):
    """Each OFL `partial_fit` holds one `engine.state` span, before its
    dispatch: the uniforms, and the carry's state joined ahead of them
    (chunks of 96, 96, 96 and 32 points against epochs of 64: the second
    and the fourth call join a carry of 32)."""
    eng = OCCEngine(OFLTransaction(1.0, 256, jax.random.key(1)), pb=64,
                    validate_cap="adaptive")
    x = _stream()
    _fit(eng, x[:192], chunk=96)                # compile outside the trace
    spans = _profiled_spans(tmp_path, lambda: _fit(eng, x[192:], chunk=96))
    calls = [(s, e) for s, e, n in spans if n == "engine.partial_fit"]
    assert len(calls) == 4
    for s0, e0 in calls:
        inside = sorted((s, n) for s, e, n in spans
                        if s0 <= s and e <= e0 and n != "engine.partial_fit")
        names = [n for _, n in inside]
        assert names.count("engine.state") == 1, names
        assert names.index("engine.state") < names.index("engine.dispatch")


def test_dp_means_state_stays_free(monkeypatch):
    """DP-means has no per-point state: its pass carries no `occ.state`
    op, and a carry joins no state, so no state program runs."""
    lowered = _engine_pass_jit.lower(
        DPMeansTransaction(1.0, k_max=64), make_pool(64, 4),
        jnp.zeros((256, 4)), (), pb=64, cap_warm=None, cap_rest=8, n_warm=1,
        n_bootstrap=0, mesh=None, data_axis="data")
    assert "occ.state" not in lowered.as_text(debug_info=True)
    joined = []
    monkeypatch.setattr("repro.core.engine._join_state",
                        lambda *a: joined.append(a))
    eng = _engine()
    _fit(eng, _stream(), chunk=96)
    assert eng.n_pending == 0 and joined == []


def test_pass_seconds_are_labelled_by_validator_width():
    """The cold pass and the retried pass are `full`, the steady passes
    `capped`; one observation per committed pass."""
    obs = Obs()
    eng = _engine(obs=obs)
    _fit(eng, _stream())
    assert eng.cap_history == [None, 8, 8, None]
    assert eng.n_cap_retries == 1
    h = {w: obs.metrics.get_histogram("engine_pass_s", width=w)
         for w in ("full", "capped")}
    assert (h["full"].count, h["capped"].count) == (2, 2)
    assert obs.metrics.get_histogram("engine_pass_s") is None
    assert obs.metrics.value("engine_passes") == 4


def test_fixed_cap_widths():
    """Without adaptive caps the width is the window's: an unbounded
    master is `full`, a window under the epoch `capped`."""
    x = _stream()[:256]
    for cap, width in ((None, "full"), (8, "capped"), (64, "full")):
        obs = Obs()
        eng = OCCEngine(DPMeansTransaction(2.0, 256), pb=64,
                        validate_cap=cap, obs=obs)
        _fit(eng, x)
        assert obs.metrics.get_histogram(
            "engine_pass_s", width=width).count == 2, cap


def test_results_bit_identical_with_and_without_obs():
    x = _stream()
    runs = []
    for obs in (None, Obs(tracer=Tracer(pid=1))):
        eng = _engine(obs=obs)
        res = _fit(eng, x)
        runs.append((eng, res))
    (e0, r0), (e1, r1) = runs
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(np.asarray(a.assign),
                                      np.asarray(b.assign))
        np.testing.assert_array_equal(np.asarray(a.send), np.asarray(b.send))
        for u, v in zip(a.stats, b.stats):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(e0.pool.centers),
                                  np.asarray(e1.pool.centers))
    assert e0.cap_history == e1.cap_history
    assert e0.n_dispatches == e1.n_dispatches == 5


def test_perfetto_trace_has_real_pass_spans_only():
    """The fused pass records `engine.pass` (its measured interval) and the
    host spans, nested; no per-epoch spans are made up for it."""
    tracer = Tracer(pid=1)
    eng = _engine(obs=Obs(tracer=tracer), publish=lambda *a, **k: None)
    _fit(eng, _stream())
    trace = tracer.to_chrome()
    assert validate_trace(trace) == []
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in events]
    assert "engine.epoch" not in names
    passes = [e for e in events if e["name"] == "engine.pass"]
    assert [e["args"]["width"] for e in passes] == ["full", "capped",
                                                    "capped", "full"]
    assert sum(e["args"]["epochs"] for e in passes) == 8
    for name in ("engine.partial_fit",) + HOST_SPANS:
        assert names.count(name) == 4, name
    assert names.count("engine.retry") == 1


def test_span_entry_point():
    """`span` is a profiler annotation alone without a tracer, and binds
    the Perfetto span when there is one."""
    with span("a") as got:
        pass
    assert isinstance(got, jax.profiler.TraceAnnotation)
    with Obs().span("a", cat="x"):
        pass
    tracer = Tracer(pid=1)
    with span("b", Obs(tracer=tracer), cat="t", k=1) as sp:
        sp.set(done=True)
    ev, = [e for e in tracer.events() if e["ph"] == "X"]
    assert (ev["name"], ev["cat"], ev["args"]) == ("b", "t",
                                                   {"k": 1, "done": True})
