"""The scope and host-span reduction (`scopes.py`) and the readers built on
it: the wire-format reader against hand-encoded XSpaces and against
`jax.profiler.ProfileData` on the recorded traces, scope booking and gap
labels on hand-made events, the new readers on a v5e trace recorded with
the scopes (`data/small_scoped.xplane.pb`, by `record_trace.py` on the
program with its `occ.*` scopes and `engine.*` spans), and nothing read
from the trace recorded before them (`data/small.xplane.pb`), on which the
older readers keep their values.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import struct
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402
import tracing  # noqa: E402

DATA = os.path.join(HERE, "data")
OLD = os.path.join(DATA, "small.xplane.pb")
SCOPED = os.path.join(DATA, "small_scoped.xplane.pb")
SPEC = common.benchmark_spec()
# The recorded traces are of an OCC DP-means job (`record_trace.py`).
DP_MEANS = common.algorithm({"algorithm": "dp_means"})
NEW = ("validate.precompute_us_per_epoch", "validate.scan_us_per_epoch",
       "validate.glue_us_per_epoch", "engine.unscoped_share",
       "engine.host_gap_share")
ONE_CHIP = [c["name"] for c in SPEC["workloads"]
            if common.mix_of(c)["shape"] == "jobs" and c["chips"] == 1]


# ---------------------------------------------------- hand-encoded XSpace

def _v(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _f(field: int, value) -> bytes:
    """One field: an int is a varint, bytes or str length-delimited, a
    float a fixed64 double."""
    if isinstance(value, int):
        return _v(field << 3) + _v(value)
    if isinstance(value, float):
        return _v(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _v(field << 3 | 2) + _v(len(value)) + value


def _plane(name, stat_names, ev_meta, lines):
    out = _f(1, 7) + _f(2, name)
    for i, (mname, stats) in ev_meta.items():
        body = _f(1, i) + _f(2, mname)
        for sid, val in stats:
            st = _f(1, sid)
            st += _f(7, val[1]) if isinstance(val, tuple) else _f(5, val)
            body += _f(5, st)
        out += _f(4, _f(1, i) + _f(2, body))
    for i, sname in stat_names.items():
        out += _f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, sname)))
    for lname, ts, evs in lines:
        body = _f(1, 1) + _f(2, lname) + _f(3, ts)
        for mid, off_ps, dur_ps in evs:
            body += _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps)
                       + _f(4, _f(1, 9) + _f(2, 1.0)))
        out += _f(3, body)
    return out


def _xspace(tmp_path, chips: int = 1) -> str:
    """A host plane with a window and engine spans, and one device plane:
    a loop without `tf_op` around a scan op (tf_op by reference), a commit,
    a precompute and a compact op, then an unscoped copy."""
    host = _plane("/host:CPU", {1: "x"},
                  {1: ("bench.window", []), 2: ("engine.dispatch", []),
                   3: ("other", [])},
                  [("main", 1000, [(1, 0, 100_000_000),
                                   (2, 1_000_000, 4_000_000),
                                   (3, 0, 1_000)])])
    path = "jit(_engine_pass)/occ.pass/while/body/closed_call"
    ops = {1: ("%while.1 = (...) while(...)", [(2, "occ.py:317")]),
           2: ("%add.1 = f32[] add(...)", [(1, ("ref", 3))]),
           3: ("%fusion.2 = f32[8] fusion(...)",
               [(1, path + "/occ.commit/scatter:")]),
           4: ("%copy.1 = f32[8] copy(...)", []),
           5: ("%fusion.3 = f32[8,8] fusion(...)",
               [(1, path + "/occ.precompute/dot_general:")]),
           6: ("%sort.1 = s32[8] sort(...)",
               [(1, path + "/occ.compact/sort:")])}
    lines = [("XLA Modules", 1000, [(1, 0, 1)]),
             ("XLA Ops", 1000, [(1, 10_000_000, 40_000_000),
                                (2, 12_000_500, 10_000_000),
                                (3, 30_000_000, 5_000_000),
                                (5, 39_000_000, 2_000_000),
                                (6, 43_000_000, 3_000_000),
                                (4, 60_000_000, 10_000_000)])]
    stat_names = {1: "tf_op", 2: "source",
                  3: path + "/occ.scan/while/body/add:"}
    devs = [_plane(f"/device:TPU:{i}", stat_names, ops, lines)
            for i in range(chips)]
    p = tmp_path / "hand.xplane.pb"
    # device planes last first: the reader orders them by index
    p.write_bytes(_f(1, host) + b"".join(_f(1, d) for d in devs[::-1])
                  + _f(2, "an error"))
    return str(p)


def test_wire_reader_on_a_hand_encoded_xspace(tmp_path):
    chips, host = scopes.read_file(_xspace(tmp_path))
    assert sorted(host) == [(1000, 101000, "bench.window"),
                            (2000, 6000, "engine.dispatch")]
    ops, = chips
    assert [o[:2] for o in ops] == [(11000, 51000), (13000, 23000),
                                    (31000, 36000), (40000, 42000),
                                    (44000, 47000), (61000, 71000)]
    assert ops[0][3:] == (None, "occ.py:317")
    assert ops[1][3].endswith("/occ.scan/while/body/add:")
    assert ops[5][3] is None


def test_scope_booking_and_gap_labels(tmp_path):
    sc = scopes.reduce_file(_xspace(tmp_path))
    ns = sc.chips[0]["scopes"]
    # the loop shares its nested ops' path up to the epoch body: occ.pass
    assert ns == {"occ.pass": 40_000 - 10_000 - 5_000 - 2_000 - 3_000,
                  "occ.scan": 10_000, "occ.commit": 5_000,
                  "occ.precompute": 2_000, "occ.compact": 3_000,
                  None: 10_000}
    assert sc.scoped and sc.has_engine_spans
    assert sc.scope_seconds(None) == pytest.approx(10e-6)
    gaps = sc.idle_gaps()
    # [1000, 11000) under the dispatch span from 2000; the rest under the
    # window alone
    assert gaps[0] == ("engine.dispatch", pytest.approx(10e-6))
    assert [k for k, _ in gaps[1:]] == ["bench.window", "bench.window"]
    assert sc.idle_under(scopes.HOST_WORK) == pytest.approx(10e-6)


@pytest.mark.parametrize("chips", [1, 2])
def test_new_readers_on_a_hand_encoded_trace(tmp_path, chips):
    """Two epochs on each chip: the validator parts per chip and epoch,
    the share of self time under no scope, and the idle time under
    dispatch, as a mean over chips."""
    path = _xspace(tmp_path, chips)
    chips, host = scopes.read_file(path)
    red = tracing.reduce_events([[(s, e, n) for s, e, n, *_ in c]
                                 for c in chips],
                                [h for h in host
                                 if h[2].startswith("bench.")])
    ctx = {"counters": {"traced_call": {"dir": str(tmp_path),
                                        "accepted": [3, 1]}},
           "trace": red}
    got = {name: run.layer_reader(name)(ctx) for name in NEW}
    assert got == {"validate.precompute_us_per_epoch": pytest.approx(1.0),
                   "validate.scan_us_per_epoch": pytest.approx(5.0),
                   "validate.glue_us_per_epoch": pytest.approx(4.0),
                   "engine.unscoped_share": pytest.approx(20.0),
                   "engine.host_gap_share": pytest.approx(10.0)}
    del ctx["counters"]["traced_call"]["dir"]      # no trace to read
    assert all(run.layer_reader(n)(ctx) is None for n in NEW)


def test_shared_path_and_scope():
    a = scopes.path_of("jit(f)/occ.pass/while/body/occ.scan/x:")
    b = scopes.path_of("jit(f)/occ.pass/while/body/occ.commit/y:")
    assert scopes.scope_of(a) == "occ.scan"
    assert scopes._shared(a, b) == ("jit(f)", "occ.pass", "while", "body")
    assert scopes.scope_of(scopes._shared(a, b)) == "occ.pass"
    assert scopes.scope_of(None) is None
    assert scopes.path_of(None) is None


# ------------------------------------------------------- recorded traces

@pytest.mark.parametrize("path", [OLD, SCOPED])
def test_wire_reader_matches_profile_data(path):
    """The same device ops (start, end, HLO name) and `bench.*` spans as
    the `ProfileData` reader of `tracing.py`."""
    chips, host = scopes.read_file(path)
    want_chips, want_host = tracing.read_file(path)
    assert [[(s, e, tracing.op_name(n)) for s, e, n, *_ in c]
            for c in chips] == want_chips
    assert sorted(h for h in host if h[2].startswith("bench.")) \
        == sorted(want_host)


def _ctx(cell, path, meta, tmp_path):
    """The context a run of the cell hands its readers, its trace
    directory holding the recorded trace."""
    tdir = tmp_path / "trace"
    tdir.mkdir()
    shutil.copy(path, tdir / "t.xplane.pb")
    red = tracing.reduce_file(path)
    c = common.find_cell(SPEC, cell)
    cfg = common.config_of(SPEC, c)
    return {"cell": c, "config": cfg, "algorithm": common.algorithm(cfg),
            "peaks": common.peaks_for(meta["device_kind"]),
            "counters": {"compiles": 0, "cap_retries": 0, "pb": meta["pb"],
                         "dim": meta["dim"], "chips": 1,
                         "traced_call": {"dir": str(tdir),
                                         "k_start": meta["k_start"],
                                         "accepted": meta["accepted"],
                                         "seconds": red.window_s}},
            "trace": red}


# What every reader read on the trace recorded before the scopes.
OLD_VALUES = {"engine.compiles": 0, "engine.cap_retries": 0,
              "propose_roofline": 17.220453677449825,
              "propose.device_share": 1.3010897159909143,
              "validate.us_per_epoch": 66.480625,
              "train.mfu": 2.4636869507769038e-05,
              "mesh.collective_share": None,
              "device.idle_share.train": 99.89903457839534}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_old_trace_reads_as_before(cell, tmp_path):
    """On the trace recorded before the scopes, the older readers keep
    their values, and the new ones read nothing."""
    meta = json.load(open(os.path.join(DATA, "small.json")))
    ctx = _ctx(cell, OLD, meta, tmp_path)
    for name, want in OLD_VALUES.items():
        got = run.layer_reader(name)(ctx)
        assert got == (want if want is None else pytest.approx(want,
                                                               rel=1e-12))
    for name in NEW:
        assert run.layer_reader(name)(ctx) is None, name


def test_scoped_trace_books_the_pass_to_its_scopes():
    """Every op of the recorded engine pass lies under an `occ.*` scope;
    each of the pass's six scopes holds device time; the `while` loops
    take their scope from what they hold."""
    chips, host = scopes.read_file(SCOPED)
    assert any(op[3] and "/occ.scan/" in op[3] for op in chips[0])
    sc = scopes.reduce_file(SCOPED)
    ns = sc.chips[0]["scopes"]
    for s in ("occ.pass", "occ.propose", "occ.compact", "occ.precompute",
              "occ.scan", "occ.commit"):
        assert ns.get(s, 0) > 0, s
    for (name, scope, tf_op, _), v in sc.chips[0]["ops"].items():
        if tf_op and "_engine_pass" in tf_op:
            assert scope is not None, tf_op
        if tracing.op_name(name).startswith("while") and v > 0:
            assert scope is not None, name
    # the propose kernel is under occ.propose
    assert any(scope == "occ.propose" and DP_MEANS.PROPOSE_KERNEL in name
               for name, scope, _, _ in sc.chips[0]["ops"])
    assert sum(ns.values()) == pytest.approx(sc.chips[0]["busy"], rel=0.05)


def test_scoped_trace_gaps_carry_engine_spans():
    sc = scopes.reduce_file(SCOPED)
    names = {n for _, _, n in sc.host}
    # the recording's engine has no publish hook
    assert {"engine.partial_fit", "engine.dispatch", "engine.stats_wait",
            "bench.window"} <= names
    labels = {k for k, _ in sc.idle_gaps()}
    assert labels & {"engine.dispatch", "engine.stats_wait",
                     "engine.partial_fit"}
    bd = sc.breakdown()
    assert set(bd) == {"window_s", "scopes_s", "ops", "idle_by_span_s",
                       "longest_gaps"}
    assert sum(bd["idle_by_span_s"].values()) == pytest.approx(
        sc.window_s - sc.chips[0]["busy"] / 1e9, rel=1e-6)


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_new_readers_on_the_scoped_trace(cell, tmp_path):
    """Each new reader reads a number in range on the scoped trace; the
    three validator parts together are the scopes' whole share of
    `validate.us_per_epoch`."""
    meta = json.load(open(os.path.join(DATA, "small_scoped.json")))
    ctx = _ctx(cell, SCOPED, meta, tmp_path)
    got = {name: run.layer_reader(name)(ctx) for name in NEW}
    for name, value in got.items():
        assert value is not None and value >= 0, name
    assert got["engine.unscoped_share"] <= 100
    assert got["engine.host_gap_share"] <= 100
    parts = (got["validate.precompute_us_per_epoch"]
             + got["validate.scan_us_per_epoch"]
             + got["validate.glue_us_per_epoch"])
    assert 0 < parts < run.layer_reader("validate.us_per_epoch")(ctx)


def test_breakdown_command(capsys):
    assert scopes.main(["scopes.py", SCOPED, "1"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["scopes_s"] and out["ops"]
