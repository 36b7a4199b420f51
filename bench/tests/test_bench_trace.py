"""The trace reduction, on a small profiler trace recorded on a TPU v5e
(``record_trace.py``): an upload and three two-query reader flushes."""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from bench import tracereduce

FIXTURE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def planes():
    return tracereduce.load(str(FIXTURE))


def _device_events(planes, line):
    dev = [p for p in planes if p.name == "/device:TPU:0"][0]
    return [e for ln in dev.lines if ln.name == line for e in ln.events]


def test_busy_is_the_union_of_device_ops(planes):
    s = tracereduce.reduce(planes)
    w0, w1 = tracereduce.window_of(planes)
    grid = np.zeros(int((w1 - w0) // 1000) + 1, bool)      # 1 us cells
    ops = _device_events(planes, "XLA Ops")
    for _, st, d in ops:
        a, b = max(st, w0), min(st + d, w1)
        if b > a:
            grid[int((a - w0) // 1000):int(np.ceil((b - w0) / 1000))] = True
    assert len(s.busy_ns) == 1
    assert 0 < s.busy_ns[0] <= s.window_ns
    assert abs(grid.sum() * 1000 - s.busy_ns[0]) <= 2000 * len(ops)
    assert s.window_ns == w1 - w0


def test_program_time_by_name(planes):
    s = tracereduce.reduce(planes)
    w0, w1 = tracereduce.window_of(planes)
    for fragment in ("hail_read_batch", "_hail_block"):
        want = sum(min(st + d, w1) - max(st, w0)
                   for n, st, d in _device_events(planes, "XLA Modules")
                   if fragment in n and st + d > w0 and st < w1)
        assert want > 0
        assert s.program_s(fragment) == pytest.approx(want / 1e9)
    assert s.program_s("no_such_program") is None


def test_idle_gaps_are_named_and_add_up(planes):
    s = tracereduce.reduce(planes)
    assert sum(s.idle_ns.values()) == pytest.approx(
        s.window_ns - s.busy_ns[0])
    assert set(s.idle_ns) <= {"bench:window", "bench:flush",
                              "bench:hail_upload", "(no host span)"}
    assert s.idle_ns.get("bench:flush", 0) > 0
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all("/" in name for name, _ in b["device_ops"])


def test_gaps_on_a_made_up_trace():
    P, L = tracereduce.Plane, tracereduce.Line
    planes = [
        P("/host:CPU", [L("python3", [("bench:window", 0.0, 50.0),
                                      ("bench:flush", 15.0, 20.0)])]),
        P("/device:TPU:0", [
            L("XLA Modules", [("jit_a(1)", 10.0, 10.0),
                              ("jit_b(2)", 30.0, 10.0)]),
            L("XLA Ops", [("%f.1 = f32[] fusion()", 10.0, 10.0),
                          ("%g = f32[] add()", 30.0, 5.0),
                          ("%h = f32[] add()", 33.0, 7.0)])])]
    s = tracereduce.reduce(planes, [("finalize", 22.0, 28.0)])
    assert s.busy_ns == [20.0] and s.window_ns == 50.0
    assert s.idle_ns == {"bench:window": 20.0, "finalize": 10.0}
    assert s.programs_ns == {"jit_a": 10.0, "jit_b": 10.0}
    assert s.ops_ns == {"jit_a/f.1": 10.0, "jit_b/g": 5.0, "jit_b/h": 7.0}


def test_merge_and_innermost():
    assert tracereduce.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4),
                                                                   (5, 8)]
    spans = [("a", 0, 10), ("b", 2, 5), ("c", 2, 4)]
    assert tracereduce.innermost(spans, 3) == "c"
    assert tracereduce.innermost(spans, 4.5) == "b"
    assert tracereduce.innermost(spans, 11) == "(no host span)"
