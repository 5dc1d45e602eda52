#!/usr/bin/env python3
"""What a profiler trace says about the program's own names: the `occ.*`
named scopes its device ops carry, and the `engine.*` host spans.

`jax.profiler.ProfileData` gives each device op its HLO text only, so this
module reads the `.xplane.pb` itself: a protocol-buffer wire-format reader
of the few XSpace fields it needs (planes, lines, events, event and stat
metadata), which takes nothing beyond the standard library.  Each device
op's metadata carries `tf_op`, the op's name-stack path (for example
`jit(_engine_pass)/occ.pass/while/body/closed_call/occ.scan/while/...:`),
and `source`.

  scope      the innermost `occ.*` segment of an op's path; an op without
             a `tf_op` (XLA's loops) takes the longest path its nested ops
             share, and is unscoped when nothing is nested in it;
  self time  an op's duration less that of the ops nested in it (as in
             `tracing.py`), booked to its scope;
  idle gaps  holes in a chip's busy union inside the `bench.window` span,
             each labelled by the innermost `engine.*` or `bench.*` host
             span around its midpoint.

A trace of a program without scopes or engine spans reads as such: the
readers of the metrics built on this return nothing there.

    python3 chipbench/scopes.py <trace dir or .xplane.pb> [n_chips]

prints the breakdown of one trace as JSON.
"""
from __future__ import annotations

import functools
import json
import os
import re
import struct
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import tracing  # noqa: E402

SCOPE = "occ."
HOST = ("engine.", "bench.")
HOST_WORK = ("engine.dispatch", "engine.retry", "engine.publish")


# ------------------------------------------------------------- wire format

def _varint(buf, i):
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    r, s = b & 0x7F, 7
    i += 1
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if b < 0x80:
            return r, i
        s += 7


def _fields(buf, i, end):
    """(field number, value) of each field of the message in buf[i:end]; a
    length-delimited value is its (start, end)."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = buf[i:i + 8]
            i += 8
        elif wire == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span):
    """(metadata id, value) of an XStat; a ref value is ('ref', id)."""
    mid, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = v
        elif f in (5, 6):
            val = _str(buf, v)
        elif f == 7:
            val = ("ref", v)
    return mid, val


def _map_entry(buf, span):
    key, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, span, want_line):
    """One XPlane: its name, the events of the lines `want_line(plane,
    line)` keeps as (start_ns, end_ns, metadata id), and its event
    metadata as id -> (name, {stat name: value})."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.update([_map_entry(buf, v)])
        elif f == 5:
            k, m = _map_entry(buf, v)
            for g, w in _fields(buf, *m):
                if g == 2:
                    stat_names[k] = _str(buf, w)
    meta = {}
    for k, span_m in ev_meta.items():
        mname, stats = "", {}
        for f, v in _fields(buf, *span_m):
            if f == 2:
                mname = _str(buf, v)
            elif f == 5:
                sid, val = _stat(buf, v)
                if isinstance(val, tuple):
                    val = stat_names.get(val[1], "")
                stats[stat_names.get(sid, str(sid))] = val
        meta[k] = (mname, stats)
    events = []
    for span_l in lines:
        lname, ts, evs = "", 0, []
        for f, v in _fields(buf, *span_l):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                ts = v
            elif f == 4:
                evs.append(v)
        if not want_line(name, lname):
            continue
        for span_e in evs:
            mid = off = dur = 0
            for f, v in _fields(buf, *span_e):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            # whole nanoseconds, as `ProfileData` gives them
            start = ts + off // 1000
            events.append((start, start + dur // 1000, mid))
    return name, events, meta


def read_file(path: str):
    """(per-chip ops, host spans) of an `.xplane.pb`: for each device plane
    `/device:TPU:<i>` in order, (start_ns, end_ns, HLO text, tf_op, source)
    of each event of its `XLA Ops` line; and (start_ns, end_ns, name) of
    every host event named `engine.*` or `bench.*`."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def want(plane, line):
        return plane.startswith("/host:") or (
            re.fullmatch(r"/device:TPU:\d+", plane) is not None
            and line == "XLA Ops")

    chips, host = {}, []
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, meta = _plane(buf, span, want)
        if name.startswith("/host:"):
            for s, e, mid in events:
                n = meta.get(mid, ("", {}))[0]
                if n.startswith(HOST):
                    host.append((s, e, n))
        elif re.fullmatch(r"/device:TPU:\d+", name):
            ops = []
            for s, e, mid in events:
                n, st = meta.get(mid, ("", {}))
                ops.append((s, e, n, st.get("tf_op"), st.get("source")))
            chips[int(name.rsplit(":", 1)[1])] = ops
    return [chips[k] for k in sorted(chips)], host


# --------------------------------------------------------------- reduction

def path_of(tf_op):
    """`a/b/c:type` -> ('a', 'b', 'c'); None for an op without one."""
    if not tf_op:
        return None
    return tuple(tf_op.rsplit(":", 1)[0].split("/"))


def scope_of(path):
    """The innermost `occ.*` segment of a path, or None."""
    for seg in reversed(path or ()):
        if seg.startswith(SCOPE):
            return seg
    return None


def _shared(a, b):
    if a is None:
        return b
    if b is None:
        return a
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


class Scoped:
    """Per-chip self time by scope, busy time and labelled idle gaps inside
    the traced window.  Built by `reduce_events`."""

    def __init__(self, chips, host, window):
        self.chips = chips          # per chip: scopes, ops, busy, gaps
        self.host = host
        self.window = window
        self.window_s = (window[1] - window[0]) / 1e9

    @property
    def scoped(self) -> bool:
        """Whether any op in the window carries an `occ.*` scope."""
        return any(k is not None for c in self.chips for k in c["scopes"])

    @property
    def has_engine_spans(self) -> bool:
        return any(n.startswith("engine.") for _, _, n in self.host)

    def scope_seconds(self, *scopes) -> float:
        """Self time under the scopes (None: under no scope), summed over
        chips."""
        return sum(c["scopes"].get(s, 0) for c in self.chips
                   for s in scopes) / 1e9

    @property
    def self_seconds(self) -> float:
        return sum(v for c in self.chips for v in c["scopes"].values()) / 1e9

    def idle_gaps(self, chip: int = 0):
        """(label, seconds) of every hole in a chip's busy union."""
        spans = sorted(self.host)
        out = []
        for s, e in self.chips[chip]["gaps"]:
            mid = (s + e) / 2
            label, best = "host idle", None
            for hs, he, name in spans:
                if hs > mid:
                    break
                if he >= mid and (best is None or hs >= best):
                    label, best = name, hs
            out.append((label, (e - s) / 1e9))
        return out

    def idle_under(self, names) -> float:
        """Device-idle seconds under the host spans `names`, mean over
        chips."""
        if not self.chips:
            return 0.0
        tot = sum(v for i in range(len(self.chips))
                  for k, v in self.idle_gaps(i) if k in names)
        return tot / len(self.chips)

    def breakdown(self, top: int = 10) -> dict:
        n = max(1, len(self.chips))
        by_scope = defaultdict(float)
        for c in self.chips:
            for k, v in c["scopes"].items():
                by_scope[str(k)] += v / n / 1e9
        ops = defaultdict(lambda: [0.0, None, None, None])
        for c in self.chips:
            for (name, scope, tf_op, source), v in c["ops"].items():
                ent = ops[tracing.op_name(name)]
                ent[0] += v / n / 1e9
                ent[1:] = [scope, tf_op, source]
        gaps = defaultdict(float)
        for k, v in self.idle_gaps() if self.chips else []:
            gaps[k] += v
        return {
            "window_s": self.window_s,
            "scopes_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "ops": [[k] + v for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]],
            "idle_by_span_s": dict(sorted(gaps.items(),
                                          key=lambda kv: -kv[1])),
            "longest_gaps": sorted(self.idle_gaps() if self.chips else [],
                                   key=lambda kv: -kv[1])[:top]}


def reduce_events(chip_events: list, host: list) -> Scoped:
    """The reduction proper, on plain tuples (see `read_file`)."""
    win = [(s, e) for s, e, n in host if n == tracing.WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {tracing.WINDOW_SPAN} span in the trace")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    chips = []
    for all_evs in chip_events:
        evs = sorted((ev for ev in all_evs if ev[1] > w0 and ev[0] < w1),
                     key=lambda t: (t[0], -t[1]))
        scopes = defaultdict(float)
        ops = defaultdict(float)
        # enclosing ops: [end, event, duration, children's ns, shared path]
        stack = []

        def close(frame):
            end, ev, dur, child, shared = frame
            path = path_of(ev[3])
            if path is None:
                path = shared
            scope = scope_of(path)
            scopes[scope] += dur - child
            ops[(ev[2], scope, ev[3], ev[4])] += dur - child
            if stack and path is not None:
                stack[-1][4] = _shared(stack[-1][4], path)

        for ev in evs:
            s, e = ev[0], ev[1]
            while stack and stack[-1][0] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3] += e - s
            stack.append([e, ev, e - s, 0.0, None])
        while stack:
            close(stack.pop())
        busy = tracing._clip(tracing._union((s, e) for s, e, *_ in evs),
                             w0, w1)
        gaps, prev = [], w0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if w1 > prev:
            gaps.append((prev, w1))
        chips.append({"scopes": dict(scopes), "ops": dict(ops),
                      "busy": tracing._length(busy), "gaps": gaps})
    return Scoped(chips, host, (w0, w1))


@functools.lru_cache(maxsize=2)
def _reduce_cached(path: str, n_chips: int | None, mtime_ns: int) -> Scoped:
    chips, host = read_file(path)
    return reduce_events(chips[:n_chips], host)


def reduce_file(path: str, n_chips: int | None = None) -> Scoped:
    """`reduce_events` of one trace file; the readers of one run share it."""
    return _reduce_cached(path, n_chips, os.stat(path).st_mtime_ns)


def of_run(ctx) -> Scoped | None:
    """The traced call of a training run, from a per-layer reader's
    context; None where the run traced no call or the trace is missing."""
    call = ctx["counters"].get("traced_call")
    red = ctx.get("trace")
    if not call or not call.get("dir") or red is None or not red.chips:
        return None
    try:
        path = tracing.find_trace(call["dir"])
    except ValueError:
        return None
    return reduce_file(path, len(red.chips))


def per_epoch_us(ctx, *scopes) -> float | None:
    """Self time under the scopes, per chip and epoch of the traced call,
    in microseconds; None where the trace has no scopes."""
    sc = of_run(ctx)
    if sc is None or not sc.scoped:
        return None
    epochs = len(ctx["counters"]["traced_call"]["accepted"])
    if not epochs:
        return None
    return 1e6 * sc.scope_seconds(*scopes) / len(sc.chips) / epochs


def main(argv) -> int:
    target = argv[1]
    path = target if target.endswith(".xplane.pb") else \
        tracing.find_trace(target)
    n = int(argv[2]) if len(argv) > 2 else None
    print(json.dumps(reduce_file(path, n).breakdown()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
