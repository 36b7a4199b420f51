"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

Planes named ``/device:TPU:<n>`` are devices.  On each, the ``XLA Ops``
line (or ``XLA Modules`` where a trace has no op line) gives the intervals
in which an operation ran; their union, clipped to the traced window, is
the device's busy time.  ``XLA Modules`` events are whole jitted programs,
summed by name (the ``(<id>)`` suffix dropped) for a program's device time.
The window is the host event the benchmark wraps around its measured
window (``bench:window``).  Each idle stretch of device 0 inside the window
is named by the innermost host span that covers its middle: the
benchmark's own ``bench:`` annotations, and any further spans handed in
(the program's ``obs`` spans, moved onto the profiler's clock).

    python bench/tracereduce.py <trace.xplane.pb>   # describe a trace
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "bench:window"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Line:
    name: str
    events: list          # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def load(path: str) -> list:
    """Planes of an ``.xplane.pb`` as plain tuples."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return [Plane(p.name, [Line(ln.name, [(e.name, float(e.start_ns),
                                           float(e.duration_ns))
                                          for e in ln.events])
                           for ln in p.lines])
            for p in data.planes]


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _enclosing(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1] + modules[i][2]:
        return program_name(modules[i][0])
    return "(no program)"


def host_events(planes) -> list:
    """(name, start, end) of every event on a non-device plane."""
    return [(n, s, s + d) for p in planes if not DEVICE.match(p.name)
            for ln in p.lines for n, s, d in ln.events]


def window_of(planes) -> tuple:
    wins = [(s, e) for n, s, e in host_events(planes) if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} event in the trace")
    return wins[0]


def innermost(spans, t: float) -> str:
    """Name of the latest-starting span covering ``t`` (shortest on ties)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or (s, -(e - s)) > best[0]):
            best = ((s, -(e - s)), name)
    return best[1] if best else "(no host span)"


@dataclasses.dataclass
class Summary:
    window_ns: float
    busy_ns: list                 # per device
    programs_ns: dict             # program name -> ns, summed over devices
    ops_ns: dict                  # op name -> ns, summed over devices
    idle_ns: dict                 # innermost host span -> idle ns (dev 0)

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def program_s(self, fragment: str):
        """Device seconds of the programs whose name holds ``fragment``,
        or None where no such program ran in the window."""
        hits = [v for k, v in self.programs_ns.items() if fragment in k]
        return sum(hits) / 1e9 if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def idle_pct(rec: dict):
    """Share of the traced window in which no operation ran on the device,
    in % (1 - busy union / window), or None where the run has no trace."""
    trace = rec.get("trace")
    if trace is None or not trace.window_ns:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)


def reduce(planes, extra_spans=()) -> Summary:
    w0, w1 = window_of(planes)
    devices = sorted((p for p in planes if DEVICE.match(p.name)),
                     key=lambda p: int(DEVICE.match(p.name).group(1)))
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    spans = [sp for sp in host_events(planes) if sp[0].startswith("bench:")]
    spans += list(extra_spans)
    busy, programs, ops = [], collections.Counter(), collections.Counter()
    idle = collections.Counter()
    for i, dev in enumerate(devices):
        lines = {ln.name: ln.events for ln in dev.lines}
        modules = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
        starts = [s for _, s, _ in modules]
        op_events = lines.get("XLA Ops") or modules
        for n, s, d in modules:
            for cs, ce in clip([(s, s + d)], w0, w1):
                programs[program_name(n)] += ce - cs
        for n, s, d in op_events:
            for cs, ce in clip([(s, s + d)], w0, w1):
                ops[f"{_enclosing(modules, starts, s)}/{op_name(n)}"] += \
                    ce - cs
        merged = merge(clip([(s, s + d) for _, s, d in op_events], w0, w1))
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            prev = w0
            for s, e in merged + [(w1, w1)]:
                if s > prev:
                    idle[innermost(spans, (prev + s) / 2)] += s - prev
                prev = max(prev, e)
    return Summary(window_ns=w1 - w0, busy_ns=busy, programs_ns=dict(programs),
                   ops_ns=dict(ops), idle_ns=dict(idle))


def describe(planes, top: int = 8) -> str:
    out = []
    for p in planes:
        out.append(f"plane {p.name!r}: {len(p.lines)} lines")
        for ln in p.lines:
            names = collections.Counter(n for n, _, _ in ln.events)
            out.append(f"  line {ln.name!r}: {len(ln.events)} events; "
                       f"top {names.most_common(top)}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
