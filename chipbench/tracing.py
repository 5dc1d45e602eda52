"""From a JAX profiler trace (`.xplane.pb`) to the numbers the per-layer
readers take.

The device planes are `/device:TPU:<i>`; their line `XLA Ops` holds one
event per executed HLO instruction, nested where an instruction contains
others (a `while` spans its body).  The benchmark's own host spans
(`jax.profiler.TraceAnnotation`, named `bench.*`) are on the host plane, on
the same clock.  The traced window is the host span `bench.window`.

  busy       union of the op intervals inside the window, per chip;
  self time  an op's duration less that of the ops nested in it;
  kernel     summed duration of the ops whose HLO name starts with the
             kernel's name (a Pallas call is named after its function);
  collective union of the intervals of collective ops;
  idle gaps  holes in the busy union, each labelled by the innermost
             `bench.*` host span around its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


class Reduced:
    """What one trace says, in seconds.  Built by `reduce_file`."""

    def __init__(self, chips: list, host_spans: list, window: tuple):
        self.window = window                    # (t0, t1) ns
        self.window_s = (window[1] - window[0]) / 1e9
        self.host_spans = host_spans            # (start, end, name) ns
        self.chips = chips                      # per device: dict
        n = max(1, len(chips))
        self.busy_s = sum(c["busy"] for c in chips) / n / 1e9

    def _per_chip_mean(self, key) -> float:
        n = max(1, len(self.chips))
        return sum(c[key] for c in self.chips) / n / 1e9

    def kernel_seconds(self, prefix: str) -> float:
        """Summed device time of the ops named `prefix...`, over all chips
        (each chip runs its own share of a sharded kernel)."""
        tot = 0.0
        for c in self.chips:
            for name, (_, dur, _) in c["ops"].items():
                if name.startswith(prefix):
                    tot += dur
        return tot / 1e9

    def kernel_count(self, prefix: str) -> int:
        return sum(cnt for c in self.chips
                   for name, (cnt, _, _) in c["ops"].items()
                   if name.startswith(prefix))

    @property
    def collective_s(self) -> float:
        return self._per_chip_mean("collective")

    @property
    def ops_on_device(self) -> int:
        return sum(cnt for c in self.chips for cnt, _, _ in c["ops"].values())

    def idle_gaps(self):
        """(label, seconds) of every hole in chip 0's busy union."""
        if not self.chips:
            return []
        spans = sorted(self.host_spans)
        out = []
        for s, e in self.chips[0]["gaps"]:
            mid = (s + e) / 2
            label, best = "host idle", None
            for hs, he, name in spans:
                if hs > mid:
                    break
                if he >= mid and (best is None or hs >= best):
                    label, best = name, hs
            out.append((label, (e - s) / 1e9))
        return out

    def breakdown(self) -> dict:
        agg = defaultdict(float)
        for c in self.chips:
            for name, (_, _, self_ns) in c["ops"].items():
                agg[re.sub(r"\.\d+$", "", name)] += self_ns / 1e9
        n = max(1, len(self.chips))
        ops = sorted(((k, v / n) for k, v in agg.items()), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:10]],
                "idle_gaps": [[k, v] for k, v in gaps[:10]]}

    def summary(self) -> dict:
        gaps = self.idle_gaps()
        by_label = defaultdict(float)
        for k, v in gaps:
            by_label[k] += v
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "ops_on_device": self.ops_on_device,
                "idle_by_host_span_s": dict(by_label)}


def _device_planes(planes):
    devs = [p for p in planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    return sorted(devs, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def read_file(path: str):
    """(per-chip op events, host spans) of an `.xplane.pb`: for each device
    plane in order, (start_ns, end_ns, op name) of its `XLA Ops` line; and
    (start_ns, end_ns, name) of every `bench.*` host span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    chips = []
    for p in _device_planes(planes):
        evs = []
        for line in p.lines:
            if line.name == "XLA Ops":
                evs = [(ev.start_ns, ev.end_ns, op_name(ev.name))
                       for ev in line.events]
        chips.append(evs)
    return chips, host


def reduce_events(chip_events: list, host: list) -> Reduced:
    """The reduction proper, on plain tuples (see `read_file`)."""
    win = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    chips = []
    for all_evs in chip_events:
        evs = sorted(((s, e, n) for s, e, n in all_evs if e > w0 and s < w1),
                     key=lambda t: (t[0], -t[1]))
        ops = {}
        stack = []              # enclosing ops: [end, name, dur, child_ns]
        for s, e, name in evs:
            while stack and stack[-1][0] <= s:
                _close(stack.pop(), ops)
            if stack:
                stack[-1][3] += e - s
            ent = ops.setdefault(name, [0, 0.0, 0.0])
            ent[0] += 1
            ent[1] += e - s
            stack.append([e, name, e - s, 0.0])
        while stack:
            _close(stack.pop(), ops)
        busy = _clip(_union((s, e) for s, e, _ in evs), w0, w1)
        gaps = []
        prev = w0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if w1 > prev:
            gaps.append((prev, w1))
        coll = _clip(_union((s, e) for s, e, n in evs if COLLECTIVE.match(n)),
                     w0, w1)
        chips.append({"busy": _length(busy), "collective": _length(coll),
                      "gaps": gaps,
                      "ops": {k: tuple(v) for k, v in ops.items()}})
    return Reduced(chips, host, (w0, w1))


def reduce_file(path: str, n_chips: int | None = None) -> Reduced:
    chips, host = read_file(path)
    return reduce_events(chips[:n_chips], host)


def _close(frame, ops):
    """Book a finished op's self time: its duration less its children's."""
    _, name, dur, child = frame
    ops[name][2] += dur - child


def find_trace(tdir: str) -> str:
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {tdir}, found "
                         f"{len(files)}")
    return files[0]


def reduce_dir(tdir: str, n_chips: int) -> Reduced:
    return reduce_file(find_trace(tdir), n_chips)
