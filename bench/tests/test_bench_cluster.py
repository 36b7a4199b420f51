"""The four-chip cell ``uservisits4.bob``: at a small size on four virtual
CPU devices (``cluster_worker.py``, a subprocess) its run reads ``correct``
true and turns false when one chip's reader output is altered or one
chip's splits are dropped; its mix is ``bob``'s; and its two per-layer
readers give known values on hand-built obs events."""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench import harness

WORKER = harness.BENCH / "tests" / "cluster_worker.py"


@pytest.fixture(scope="module")
def readings():
    r = subprocess.run([sys.executable, str(WORKER)], cwd=harness.ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            out[rec["reading"]] = rec
    return out


def test_cluster_run_is_correct(readings):
    run = readings["run"]
    assert run["attempted"] > 0
    assert run["correct"] is True, run["checks"]


@pytest.mark.parametrize("fault", ["altered_on_one_chip",
                                   "one_chip_dropped"])
def test_cluster_fault_is_not_correct(readings, fault):
    assert readings[fault]["correct"] is False, readings[fault]["checks"]


def test_cluster_metrics_read_on_four_chips(readings):
    m = readings["metrics"]
    assert sorted(m["chip_blocks"]) == ["0", "1", "2", "3"]
    assert 0 < m["metrics"]["chip_balance_pct"] <= 100
    assert 1 <= m["metrics"]["chips_in_flight"] <= 4


def test_cluster_setup_refuses_a_program_without_placed_upload(
        monkeypatch):
    """A program whose upload cannot place a store fails at once, before
    any data is made."""
    from bench.tests.smallcells import small_cell
    from repro.core import upload as up

    def one_device_upload(schema, raw_blocks, sort_keys=None, **kw):
        raise AssertionError("set-up went on to the upload")
    monkeypatch.setattr(up, "hail_upload", one_device_upload)
    cell = small_cell("uservisits4.bob")
    with pytest.raises(TypeError, match="devices"):
        cell.loop.setup(harness.Context(cell, 1, lambda _: None))


def test_bob4_is_bob_on_the_cluster_loop():
    bob = json.loads((harness.BENCH / "traffic" / "bob.json").read_text())
    bob4 = json.loads((harness.BENCH / "traffic" / "bob4.json").read_text())
    assert bob4["templates"] == bob["templates"]
    assert bob4["warm_rounds"] == bob["warm_rounds"]
    assert bob4["loop"] == "cluster_flush"


def _span(name, start_us, end_us, **args):
    return [{"ph": "B", "pid": 1, "tid": 1, "name": name,
             "ts": float(start_us), "args": args},
            {"ph": "E", "pid": 1, "tid": 1, "name": name,
             "ts": float(end_us)}]


def _events():
    """Two flushes over chips 0-3.  The first dispatches 4 blocks on chip
    0, 2 on chip 1 and 2 on chip 2 (a dead split of 6 blocks on chip 3 is
    not issued); its issues go to chips 0, 1, 0, 2, then all four are
    waited on.  The second dispatches 3 blocks on each chip, issued 0-3
    with a wait on chip 0 before the issue on chip 3."""
    ev = []
    ev += _span("flush", 0, 100)
    for s, chip, blocks, live in ((1, 0, 2, [0]), (3, 1, 2, [0]),
                                  (5, 0, 2, [1]), (7, 2, 2, [0]),
                                  (9, 3, 6, [])):
        ev += _span("dispatch", s, s + 2, chip=chip,
                    blocks=list(range(blocks)), live=live)
    for s, chip in ((2, 0), (4, 1), (6, 0), (8, 2)):
        ev += _span("issue", s, s + 0.5, chip=chip)
    for s, chip in ((20, 0), (21, 1), (22, 0), (23, 2)):
        ev += _span("wait", s, s + 0.5, chip=chip)
    ev += _span("flush", 200, 300)
    for s, chip in ((201, 0), (203, 1), (205, 2), (209, 3)):
        ev += _span("dispatch", s, s + 2, chip=chip, blocks=[0, 1, 2],
                    live=[2])
        ev += _span("issue", s + 1, s + 1.5, chip=chip)
    ev += _span("wait", 207, 208, chip=0)
    for s, chip in ((220, 1), (221, 2), (222, 3)):
        ev += _span("wait", s, s + 0.5, chip=chip)
    return ev


# metric -> its reading of _events()
EXPECTED = {
    # flush 1: blocks (4, 2, 2, 0), mean 2 over max 4; flush 2: even
    "chip_balance_pct": (100.0 * 2 / 4 + 100.0) / 2,
    # flush 1: 1, 2, 2 (chip 0 again), 3 chips; flush 2: 1, 2, 3, then 3
    # (chip 0 waited on before chip 3's issue)
    "chips_in_flight": (1 + 2 + 2 + 3 + 1 + 2 + 3 + 3) / 8,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_cluster_span_reader(name):
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"bench_metric_{name}").read
    assert read({"obs_events": _events()}) == pytest.approx(EXPECTED[name])
    # spans without a chip (a program that lacks the argument), or none
    no_chip = [e for e in _events() if e.get("name") == "flush"]
    no_chip += _span("dispatch", 1, 2, blocks=[0], live=[0])
    no_chip += _span("issue", 1, 2)
    assert read({"obs_events": no_chip}) is None
    assert read({}) is None
