"""The reader of the propose kernel's exact fallback
(`propose.exact_fallback_share`) on hand-encoded traces: the exact kernel
alone reads 100%, the screen alone 0%, and a mix its share of epochs; a
kernel outside the `occ.propose` scope does not count.

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

READER = "propose.exact_fallback_share"
DP_MEANS = common.algorithm({"algorithm": "dp_means"})
PROPOSE = "jit(_engine_pass)/occ.pass/while/body/closed_call/occ.propose"
OPS = {
    "screen": ("%dpmeans_assign_screen.4 = (f32[256,1]) custom-call(...)",
               PROPOSE + "/jit(screened_assign)/jit(dpmeans_assign_screen)"
               "/pallas_call:"),
    "exact": ("%dpmeans_assign.1 = (f32[256,1]) custom-call(...)",
              PROPOSE + "/jit(screened_assign)/cond/branch_0_fun"
              "/jit(dpmeans_assign)/pallas_call:"),
    "serve": ("%dpmeans_assign.2 = (f32[8,1]) custom-call(...)",
              "jit(_assign_step)/jit(dpmeans_assign)/pallas_call:"),
}
KINDS = list(OPS)


def _trace(tmp_path, chips):
    """A 1 ms window; chip i runs its list of kernels 10 µs apart."""
    host = ts._plane("/host:CPU", {1: "x"}, {1: ("bench.window", [])},
                     [("main", 1000, [(1, 0, 1_000_000_000)])])
    meta = {i + 1: (OPS[k][0], [(1, OPS[k][1])]) for i, k in enumerate(KINDS)}
    devs = []
    for i, kernels in enumerate(chips):
        evs = [(KINDS.index(k) + 1, 10_000_000 * j, 5_000_000)
               for j, k in enumerate(kernels)]
        devs.append(ts._plane(f"/device:TPU:{i}", {1: "tf_op"}, meta,
                              [("XLA Ops", 1000, evs)]))
    tdir = tmp_path / "trace"
    tdir.mkdir()
    p = tdir / "t.xplane.pb"
    p.write_bytes(ts._f(1, host) + b"".join(ts._f(1, d) for d in devs))
    return str(tdir)


def _ctx(tmp_path, chips, epochs):
    tdir = _trace(tmp_path, chips)
    dev, host = scopes.read_file(tracing.find_trace(tdir))
    red = tracing.reduce_events([[(s, e, tracing.op_name(n))
                                  for s, e, n, *_ in c] for c in dev], host)
    return {"counters": {"traced_call": {"dir": tdir,
                                         "accepted": [0] * epochs}},
            "trace": red, "algorithm": DP_MEANS}


@pytest.mark.parametrize("chips,epochs,share", [
    # the parent's program: the exact kernel every epoch
    ([["exact"] * 4], 4, 100.0),
    ([["exact"] * 4] * 4, 4, 100.0),
    # every row of every epoch certified: the screen alone
    ([["screen"] * 4], 4, 0.0),
    ([["screen"] * 4] * 4, 4, 0.0),
    # one epoch of four falls back: the screen ran, then the exact kernel
    ([["screen"] * 4 + ["exact"]], 4, 25.0),
    # on four chips, one chip's one epoch of two
    ([["screen", "exact", "screen"]] + [["screen"] * 2] * 3, 2, 12.5),
    # an assign the service dispatched is no propose epoch
    ([["screen"] * 2 + ["serve"]], 2, 0.0),
    ([["exact"] * 2 + ["serve"]], 2, 100.0),
])
def test_exact_fallback_share(tmp_path, chips, epochs, share):
    got = run.layer_reader(READER)(_ctx(tmp_path, chips, epochs))
    assert got == pytest.approx(share)


def test_reads_nothing_without_a_trace_a_kernel_or_the_scope(tmp_path):
    ctx = _ctx(tmp_path, [["exact"]], 1)
    read = run.layer_reader(READER)
    assert read(dict(ctx, counters={})) is None
    assert read(dict(ctx, algorithm=type("A", (), {"PROPOSE_KERNEL": None})
                     )) is None
    (tmp_path / "u").mkdir()
    assert read(_ctx(tmp_path / "u", [["serve"]], 1)) is None


def test_kernel_time_counts_the_screen_and_the_fallback(tmp_path):
    """`propose_roofline` and `propose.device_share` book both kernels by
    the prefix: 5 µs of screen and 5 µs of exact kernel."""
    ctx = _ctx(tmp_path, [["screen", "exact"]], 1)
    assert ctx["trace"].kernel_seconds(DP_MEANS.PROPOSE_KERNEL) == \
        pytest.approx(10e-6)


@pytest.mark.parametrize("name", ["small", "small_scoped"])
def test_recorded_traces(name, tmp_path):
    """The recorded traces are of a program without the screen: the scoped
    one reads 100% (its serving assign left out), the one recorded before
    the scopes nothing."""
    meta = json.load(open(os.path.join(ts.DATA, name + ".json")))
    ctx = ts._ctx("deep96.train", os.path.join(ts.DATA, name + ".xplane.pb"),
                  meta, tmp_path)
    got = run.layer_reader(READER)(ctx)
    assert got == (pytest.approx(100.0) if name == "small_scoped" else None)


def test_the_training_cells_report_the_reader():
    for cell in ts.SPEC["workloads"]:
        e2e = {m["name"] for m in run.metrics_of(ts.SPEC["end_to_end"],
                                                 cell)}
        names = {m["name"] for m in run.metrics_of(ts.SPEC["per_layer"],
                                                   cell, e2e)}
        assert (READER in names) == ("train_points_per_s" in e2e)
