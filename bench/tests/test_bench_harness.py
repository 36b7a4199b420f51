"""The benchmark harness: every cell resolves, data and queries follow the
seed, the reference finds a wrong answer, and no result without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import datagen, harness, querygen, reference
from bench.tests.smallcells import small_cell

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.resolve(BENCH, name)
    assert cell.loop.__name__.startswith("bench_loop_")
    for fn in ("setup", "window", "check", "control", "release"):
        assert callable(getattr(cell.loop, fn))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for entry, read in cell.per_layer:
        assert entry["moves"] in names
        assert read({}) is None        # nothing to read: no number
    harness.program_schema(cell.config)


def test_unknown_names_fail(tmp_path):
    with pytest.raises(harness.BenchError):
        harness.resolve(BENCH, "no.such.cell")
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["traffic"] = "no_such_mix"
    with pytest.raises(harness.BenchError):
        harness.resolve(bad, bad["workloads"][0]["name"])
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["config"] = "no_such_config"
    with pytest.raises(harness.BenchError):
        harness.resolve(bad, bad["workloads"][0]["name"])
    bad = json.loads(json.dumps(BENCH))
    bad["per_layer"].append({"name": "no_such_metric", "unit": "%",
                             "better": "lower", "source": "device_trace",
                             "layer": "device", "moves": "qps",
                             "workloads": [CELLS[0]]})
    with pytest.raises(harness.BenchError):
        harness.resolve(bad, CELLS[0])


def test_benchmark_file_names():
    """Every file the benchmark names lies under its paths."""
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.isfile(harness.ROOT / c["file"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", ["uservisits.bob", "synthetic.scan"])
def test_same_seed_same_data_and_queries(name):
    cell = small_cell(name)
    cfg = cell.config
    t1, c1, b1 = datagen.make_table(cfg, 2 ** 33 + 5)
    t2, c2, b2 = datagen.make_table(cfg, 2 ** 33 + 5)
    t3, c3, _ = datagen.make_table(cfg, 6)
    assert np.array_equal(np.asarray(t1), np.asarray(t2))
    assert all(np.array_equal(c1[k], c2[k]) for k in c1)
    assert np.array_equal(b1, b2)
    assert not np.array_equal(c1[cfg["columns"][0]["name"]],
                              c3[cfg["columns"][0]["name"]])
    s1 = querygen.QueryStream(cell.traffic, cfg, c1, b1, 2 ** 33 + 5)
    s2 = querygen.QueryStream(cell.traffic, cfg, c2, b2, 2 ** 33 + 5)
    s3 = querygen.QueryStream(cell.traffic, cfg, c3, b1, 6)
    r1 = [s1.next_round() for _ in range(3)]
    assert r1 == [s2.next_round() for _ in range(3)]
    r3 = [s3.next_round() for _ in range(3)]
    assert r1 != r3
    # every round, on every seed, holds the same templates in one order
    shape = [t["name"] for t in cell.traffic["templates"]
             for _ in range(t["per_round"])]
    assert all([q.template for q in r] == shape for r in r1 + r3)
    assert s1.clients == len(shape)


def test_text_parses_back_to_the_columns():
    """The device-made text holds the generator's values; bad rows carry an
    ``x`` and are the generator's bad set."""
    cell = small_cell("uservisits.bob")
    cfg = dict(cell.config, bad_fraction=0.01)
    text, cols, bad = datagen.make_table(cfg, 3)
    raw = np.asarray(text).reshape(len(bad), -1)
    assert (raw[:, -1] == ord("\n")).all()
    assert bad.any()
    assert np.array_equal((raw == ord("x")).any(axis=1), bad)
    good = raw[~bad, :-1].astype(np.int64) - ord("0")
    for i, c in enumerate(cfg["columns"]):
        digits = good[:, 10 * i:10 * (i + 1)]
        vals = (digits * 10 ** np.arange(9, -1, -1)).sum(axis=1)
        assert np.array_equal(vals, cols[c["name"]][~bad])


def test_reference_rejects_dropped_row_and_changed_value():
    cols = {"k": np.array([5, 1, 7, 3, 9, 5], np.int32),
            "v": np.arange(6, dtype=np.int32) * 10}
    bad = np.array([False, False, False, False, False, True])
    q = querygen.Query("t", "k", 3, 7, ("v",))
    want = reference.answer(cols, bad, q)
    assert list(want) == [0, 2, 3]
    good = {reference.ROWID: want[::-1].copy(), "v": cols["v"][want[::-1]]}
    assert reference.compare_answer(cols, want, good, ("v",)) == (0, 0)
    dropped = {reference.ROWID: want[:2], "v": cols["v"][want[:2]]}
    assert reference.compare_answer(cols, want, dropped, ("v",))[0] == 1
    changed = {reference.ROWID: want, "v": cols["v"][want] + 1}
    assert reference.compare_answer(cols, want, changed, ("v",)) == (0, 3)
    extra = {reference.ROWID: np.array([0, 2, 3, 5]),
             "v": cols["v"][[0, 2, 3, 5]]}
    assert reference.compare_answer(cols, want, extra, ("v",))[0] == 1


def test_reference_checksums_detect_order():
    col = np.arange(2 * 1024, dtype=np.int32).reshape(2, 1024)
    s = reference.checksums(col)
    assert s.shape == (2, 8) and s.dtype == np.uint32
    swapped = col.copy()
    swapped[0, [3, 4]] = swapped[0, [4, 3]]
    assert (reference.checksums(swapped) != s).sum() == 1


def test_run_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "correct" not in r.stdout


@pytest.mark.parametrize("name", ["uservisits.bob", "synthetic.scan"])
def test_warm_up_leaves_no_compile_in_the_window(name):
    """The warm-up goes through ``submit``/``flush`` alone; its rounds have
    the window's shape, so the window compiles nothing."""
    from bench.stats import compile_counter
    cell = small_cell(name)
    counter = compile_counter()
    state = cell.loop.setup(harness.Context(cell, 11, lambda _: None))
    try:
        before = counter.snapshot()["compiles"]
        rec = cell.loop.window(state, 0.3)
        assert rec["attempted"] > 0
        assert counter.snapshot()["compiles"] == before
    finally:
        cell.loop.release(state)
