"""The readers of the per-point state layer (`state.us_per_epoch`,
`engine.state_gap_share`): on a hand-encoded trace that has the `occ.state`
scope and the `engine.state` span they read its device time and its idle
gap; on traces of a program without them (the recorded DP-means traces,
which predate them) they read nothing, and do not raise.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import json
import os

import pytest

import test_scopes as ts  # puts chipbench/ on the path

import common  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402
import tracing  # noqa: E402

READERS = ("state.us_per_epoch", "engine.state_gap_share")
CELL = "deep96-ofl.train"


def _xspace(tmp_path, chips: int) -> str:
    """A 100 µs window; the host draws the state from 1 to 9 µs into it;
    each chip idles until 10 µs, runs the state draw (2 µs, `occ.state`)
    and then one op of the pass (20 µs, `occ.scan`)."""
    host = ts._plane("/host:CPU", {1: "x"},
                     {1: ("bench.window", []), 2: ("engine.state", []),
                      3: ("engine.partial_fit", [])},
                     [("main", 1000, [(1, 0, 100_000_000),
                                      (3, 0, 90_000_000),
                                      (2, 1_000_000, 8_000_000)])])
    ops = {1: ("%fusion.1 = f32[8] fusion(...)",
               [(1, "jit(_draw_uniforms)/occ.state/threefry2x32:")]),
           2: ("%fusion.2 = f32[8] fusion(...)",
               [(1, "jit(_engine_pass)/occ.pass/while/body/occ.scan/add:")])}
    lines = [("XLA Ops", 1000, [(1, 10_000_000, 2_000_000),
                                (2, 12_000_000, 20_000_000)])]
    devs = [ts._plane(f"/device:TPU:{i}", {1: "tf_op"}, ops, lines)
            for i in range(chips)]
    p = tmp_path / "state.xplane.pb"
    p.write_bytes(ts._f(1, host) + b"".join(ts._f(1, d) for d in devs))
    return str(p)


def _ctx(path, tmp_path, chips, epochs=(3, 1)):
    dev, host = scopes.read_file(path)
    red = tracing.reduce_events([[(s, e, n) for s, e, n, *_ in c]
                                 for c in dev[:chips]],
                                [h for h in host
                                 if h[2].startswith("bench.")])
    return {"counters": {"traced_call": {"dir": str(tmp_path),
                                         "accepted": list(epochs)}},
            "trace": red}


def test_state_ahead_of_the_window_is_booked(tmp_path):
    """The device's clock can put the call's first op, the state draw,
    ahead of the `bench.window` span: it still counts, as the trace holds
    the traced call alone."""
    host = ts._plane("/host:CPU", {1: "x"}, {1: ("bench.window", [])},
                     [("main", 1000, [(1, 5_000_000, 95_000_000)])])
    ops = {1: ("%fusion.1 = f32[8] fusion(...)",
               [(1, "jit(_draw_uniforms)/occ.state/threefry2x32:")]),
           2: ("%fusion.2 = f32[8] fusion(...)",
               [(1, "jit(_engine_pass)/occ.pass/while/body/occ.scan/add:")])}
    lines = [("XLA Ops", 1000, [(1, 1_000_000, 3_000_000),
                                (2, 12_000_000, 20_000_000)])]
    p = tmp_path / "ahead.xplane.pb"
    p.write_bytes(ts._f(1, host) + ts._f(1, ts._plane(
        "/device:TPU:0", {1: "tf_op"}, ops, lines)))
    ctx = _ctx(str(p), tmp_path, 1, epochs=(1, 1, 1))
    assert scopes.reduce_file(str(p)).scope_seconds("occ.state") == 0
    assert run.layer_reader("state.us_per_epoch")(ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("chips", [1, 2])
def test_state_readers_on_a_hand_encoded_trace(tmp_path, chips):
    """2 µs under `occ.state` on each chip over two epochs; the first 10 µs
    of the 100 µs window idle, the host inside `engine.state` at their
    midpoint."""
    ctx = _ctx(_xspace(tmp_path, chips), tmp_path, chips)
    got = {name: run.layer_reader(name)(ctx) for name in READERS}
    assert got == {"state.us_per_epoch": pytest.approx(1.0),
                   "engine.state_gap_share": pytest.approx(10.0)}
    del ctx["counters"]["traced_call"]["dir"]      # no trace to read
    assert all(run.layer_reader(n)(ctx) is None for n in READERS)


@pytest.mark.parametrize("name", ["small", "small_scoped"])
def test_state_readers_read_nothing_without_the_scope(name, tmp_path):
    """The recorded traces are of a program that draws no state under
    `occ.state` and has no `engine.state` span, as a program older than
    the readers: they read nothing there."""
    meta = json.load(open(os.path.join(ts.DATA, name + ".json")))
    path = os.path.join(ts.DATA, name + ".xplane.pb")
    ctx = ts._ctx(CELL, path, meta, tmp_path)
    assert scopes.of_run(ctx) is not None
    assert all(run.layer_reader(n)(ctx) is None for n in READERS)


def test_the_cell_reports_the_state_readers():
    cell = common.find_cell(ts.SPEC, CELL)
    e2e = {m["name"] for m in run.metrics_of(ts.SPEC["end_to_end"], cell)}
    names = {m["name"] for m in run.metrics_of(ts.SPEC["per_layer"], cell,
                                               e2e)}
    assert set(READERS) <= names and "mesh.collective_share" not in names
    for other in ("deep96.train", "laion512.train", "laion512.train.4chip"):
        c = common.find_cell(ts.SPEC, other)
        assert not set(READERS) & {
            m["name"] for m in run.metrics_of(ts.SPEC["per_layer"], c, e2e)}
