"""The reduction from trace to metrics: on hand-made events, and on small
traces recorded on a TPU v5e (`data/small.xplane.pb`, and
`data/small_scoped.xplane.pb` with the program's `occ.*` scopes and
`engine.*` spans, written by `record_trace.py`, with the counts each saw in
`data/<name>.json`).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import flops  # noqa: E402
import run  # noqa: E402
import tracing  # noqa: E402

DATA = os.path.join(HERE, "data")
SPEC = common.benchmark_spec()
# The recorded traces are of an OCC DP-means job.
DP_MEANS = common.algorithm({"algorithm": "dp_means"})
PROPOSE_READERS = ("propose_roofline", "propose.device_share",
                   "validate.us_per_epoch", "train.mfu")

# One window of 100 ns; two chips.  Chip 0: a loop holding the kernel and a
# fusion, an all-gather, a lone fusion, and a copy that runs past the
# window's end.  Chip 1: one op.
HOST = [(0, 100, "bench.window"), (10, 30, "bench.partial_fit"),
        (60, 90, "bench.sleep")]
CHIP0 = [(5, 50, "while.1"), (10, 20, "dpmeans_assign.3"),
         (25, 30, "fusion.2"), (55, 58, "all-gather.1"), (70, 80, "fusion.4"),
         (95, 120, "copy.1"), (200, 300, "fusion.9")]
CHIP1 = [(0, 40, "fusion.1")]


def test_busy_union_gaps_and_idle_share():
    red = tracing.reduce_events([CHIP0, CHIP1], HOST)
    assert red.window_s == pytest.approx(100e-9)
    # chip 0: [5,50] [55,58] [70,80] [95,100] = 63 ns; chip 1: 40 ns
    assert red.chips[0]["busy"] == 63
    assert red.chips[1]["busy"] == 40
    assert red.busy_s == pytest.approx((63 + 40) / 2 * 1e-9)
    assert red.chips[0]["gaps"] == [(0, 5), (50, 55), (58, 70), (80, 95)]


def test_self_time_kernel_time_and_collectives():
    red = tracing.reduce_events([CHIP0], HOST)
    ops = red.chips[0]["ops"]
    assert ops["while.1"] == (1, 45, 45 - 10 - 5)
    assert ops["dpmeans_assign.3"] == (1, 10, 10)
    assert "fusion.9" not in ops                 # outside the window
    assert red.kernel_seconds("dpmeans_assign") == pytest.approx(10e-9)
    assert red.kernel_count("dpmeans_assign") == 1
    assert red.collective_s == pytest.approx(3e-9)


def test_idle_gaps_take_the_innermost_host_span():
    red = tracing.reduce_events([CHIP0], HOST)
    assert red.idle_gaps() == [
        ("bench.window", pytest.approx(5e-9)),
        ("bench.window", pytest.approx(5e-9)),
        ("bench.sleep", pytest.approx(12e-9)),
        ("bench.sleep", pytest.approx(15e-9))]
    bd = red.breakdown()
    assert bd["idle_gaps"][0] == ["bench.sleep", pytest.approx(15e-9)]
    assert len(bd["device_ops"]) <= 10


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce_events([CHIP0], HOST[1:])


def test_algorithmic_counts():
    # epochs at K_e = 5, 5 + 2, 5 + 2 + 0 centers, pb = 4 points of D = 3
    f, b = flops.propose_epochs([5, 2, 0, 9], pb=4, d=3)
    assert list(f[1:]) == [2 * 4 * 5 * 3, 2 * 4 * 7 * 3, 2 * 4 * 7 * 3]
    assert list(b[1:]) == [4 * 3 * (5 + 4), 4 * 3 * (7 + 4), 4 * 3 * (7 + 4)]
    f, b = flops.serve_dispatch([3, 64], k=1000, d=96)
    assert list(f) == [2 * 3 * 1000 * 96, 2 * 64 * 1000 * 96]
    assert list(b) == [4 * 96 * 1003, 4 * 96 * 1064]
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.min_seconds([1000, 10], [50, 50], peaks) == 10 + 5


def test_dp_means_propose_work_is_propose_epochs():
    """DP-means' propose phase is the nearest-center count, epoch by epoch
    of the traced call."""
    for k0, acc, pb, d in ((5, np.array([2, 0, 9]), 4, 3),
                           (33986, [3, 0, 1], 256, 96)):
        f, b = DP_MEANS.propose_work(k0, acc, pb, d)
        want_f, want_b = flops.propose_epochs([k0] + list(acc), pb, d)
        assert list(f) == list(want_f[1:]) and list(b) == list(want_b[1:])


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        common.peaks_for("no such chip")
    assert common.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


def test_recorded_chip_trace():
    meta = json.load(open(os.path.join(DATA, "small.json")))
    red = tracing.reduce_file(os.path.join(DATA, "small.xplane.pb"))
    assert len(red.chips) == 1
    assert 0 < red.busy_s < red.window_s
    # one propose kernel per epoch, one assign and one top-k dispatch
    assert red.kernel_count(DP_MEANS.PROPOSE_KERNEL) == meta["epochs"] + 1
    assert red.kernel_count("topk_stream") == 1
    t = red.kernel_seconds(DP_MEANS.PROPOSE_KERNEL)
    assert 0 < t < red.busy_s
    f, b = flops.propose_epochs([meta["k_start"]] + meta["accepted"],
                                meta["pb"], meta["dim"])
    share = flops.min_seconds(f[1:], b[1:],
                              common.peaks_for(meta["device_kind"])) / t
    assert 0 < share < 1
    gaps = red.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert {k for k, _ in gaps} <= {"bench.window", "bench.partial_fit",
                                    "bench.pull", "bench.submit"}
    self_total = sum(v[2] for v in red.chips[0]["ops"].values()) / 1e9
    assert self_total == pytest.approx(red.busy_s, rel=0.05)
    assert np.isfinite(red.collective_s) and red.collective_s == 0


def _recorded_ctx(cell: dict, name: str, tmp_path) -> dict:
    """The context a run of the cell hands its readers, filled from what
    the recorded trace `data/<name>.xplane.pb` ran, with that trace as the
    traced call's directory."""
    meta = json.load(open(os.path.join(DATA, name + ".json")))
    path = os.path.join(DATA, name + ".xplane.pb")
    tdir = tmp_path / name
    tdir.mkdir()
    shutil.copy(path, tdir / "t.xplane.pb")
    red = tracing.reduce_file(path)
    if common.mix_of(cell)["shape"] == "jobs":
        counters = {"compiles": 0, "cap_retries": 0, "pb": meta["pb"],
                    "dim": meta["dim"], "chips": 1,
                    "traced_call": {"dir": str(tdir),
                                    "k_start": meta["k_start"],
                                    "accepted": meta["accepted"],
                                    "seconds": red.window_s}}
    else:
        rows = meta["serve_rows"]
        counters = {"compiles": 0, "bucket_fill": 0.5, "late_p95_ms": 0.5,
                    "group_rows": [rows] * meta["serve_dispatches"],
                    "n_centers": meta["k_start"], "dim": meta["dim"],
                    "chips": 1}
    cfg = common.config_of(SPEC, cell)
    return {"cell": cell, "config": cfg, "algorithm": common.algorithm(cfg),
            "peaks": common.peaks_for(meta["device_kind"]),
            "counters": counters, "trace": red}


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]
                                  if c["chips"] == 1])
def test_every_reader_of_a_cell_reads_the_recorded_trace(cell, tmp_path):
    """Each per-layer metric a one-chip cell reports has its reader, and on
    a recorded trace it reads a number in range; a share is never above
    100%.  A reader of the program's scopes and engine spans (`scopes.py`)
    reads the trace recorded with them, every other reader the trace
    recorded before them.  Only the collective share, a several-chip
    metric, reads nothing on one chip."""
    c = common.find_cell(SPEC, cell)
    old = _recorded_ctx(c, "small", tmp_path)
    scoped = _recorded_ctx(c, "small_scoped", tmp_path)
    e2e = {m["name"] for m in run.metrics_of(SPEC["end_to_end"], c)}
    readers = run.metrics_of(SPEC["per_layer"], c, e2e)
    assert readers
    for m in readers:
        read = run.layer_reader(m["name"])
        value = read(scoped if "scopes" in read.__globals__ else old)
        if m["name"] == "mesh.collective_share":
            assert value is None
            continue
        assert value is not None and np.isfinite(value), m["name"]
        if m["unit"] == "%":
            assert 0 <= value <= 100, (m["name"], value)


def test_readers_take_the_work_and_kernel_from_the_algorithm(tmp_path):
    """The propose readers read the cell's algorithm: one with no Pallas
    propose (the test-only BP-means file) has no kernel roofline, share or
    rest of the pass, and its own count of work in `train.mfu`."""
    ctx = _recorded_ctx(common.find_cell(SPEC, "deep96.train"), "small",
                        tmp_path)
    dp = {n: run.layer_reader(n)(ctx) for n in PROPOSE_READERS}
    assert all(v is not None and v > 0 for v in dp.values()), dp
    ctx["algorithm"] = common.algorithm(
        {"algorithm": "bp_means"}, os.path.join(HERE, "algorithms"))
    bp = {n: run.layer_reader(n)(ctx) for n in PROPOSE_READERS}
    assert bp == {"propose_roofline": None, "propose.device_share": None,
                  "validate.us_per_epoch": None,
                  "train.mfu": pytest.approx(2 * dp["train.mfu"],
                                             rel=1e-12)}
