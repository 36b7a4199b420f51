"""The readers of the program's obs spans, on hand-built event lists with
known answers; each reads nothing where the list has no such span."""
from __future__ import annotations

import pytest

from bench import harness


def _span(name, start_us, end_us, tid=1, **args):
    return [{"ph": "B", "pid": 1, "tid": tid, "name": name,
             "ts": float(start_us), "args": args},
            {"ph": "E", "pid": 1, "tid": tid, "name": name,
             "ts": float(end_us)}]


def _events():
    """Two flushes.  The first: one batch of tickets 0-2 starting 1 ms in,
    two splits (dispatches of 3 and 5 ms, gathers of 1 and 2 ms), three
    finalizes of 10, 20 and 30 ms.  The second: batches of tickets 3-4 and
    5 starting 2 and 4 ms in, one split each."""
    ev = [{"ph": "M", "pid": 1, "tid": 0, "ts": 0, "name": "process_name",
           "args": {"name": "hail"}},
          {"ph": "i", "pid": 1, "tid": 2, "name": "finalize", "ts": 5.0,
           "s": "t"}]
    ev += _span("flush", 0, 100_000, queries=3)
    ev += _span("batch", 1_000, 90_000, width=3, tickets=[0, 1, 2])
    ev += _span("dispatch", 2_000, 5_000, split=0, live=[0, 1, 2])
    ev += _span("gather", 2_500, 3_500, cache_hits=1, cache_misses=0)
    ev += _span("dispatch", 5_000, 10_000, split=1, live=[0, 1])
    ev += _span("gather", 5_500, 7_500, cache_hits=0, cache_misses=1)
    for k, (s, e, d2h, ans) in enumerate([(10_000, 20_000, 3_000, 40),
                                          (20_000, 40_000, 100, 10),
                                          (40_000, 70_000, 100, 50)]):
        ev += _span("finalize", s, e, ticket=k, rows=ans // 8,
                    d2h_bytes=d2h, answer_bytes=ans)
    ev += _span("flush", 200_000, 300_000, queries=3)
    ev += _span("batch", 202_000, 250_000, width=2, tickets=[3, 4])
    ev += _span("dispatch", 203_000, 204_000, split=0, live=[3, 4])
    ev += _span("batch", 204_000, 290_000, width=1, tickets=[5])
    ev += _span("dispatch", 205_000, 206_000, split=0, live=[5])
    return ev


# metric -> its reading of _events()
EXPECTED = {
    "dispatch_ms": (3 + 5 + 1 + 1) / 4,
    "gather_ms": (1 + 2) / 2,
    "finalize_ms": (10 + 20 + 30) / 3,
    "d2h_useful_pct": 100.0 * (40 + 10 + 50) / (3_000 + 100 + 100),
    "batch_wait_ms": (3 * 1 + 2 * 2 + 1 * 4) / 6,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reader(name):
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"bench_metric_{name}").read
    assert read({"obs_events": _events()}) == pytest.approx(EXPECTED[name])
    # no such span (a program that lacks the span, or an untraced run)
    others = [e for e in _events()
              if e.get("name") in ("flush", "plan") or e["ph"] == "M"]
    assert read({"obs_events": others}) is None
    assert read({}) is None
