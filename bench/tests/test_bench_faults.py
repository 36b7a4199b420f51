"""``correct`` must come out false when the timed path is broken, and the
control (the reference with one stated guarantee broken, in the program's
place) must fail the comparison.  Runs on the CPU at a small size: the
harness's look for a chip is skipped, the rest of a run is driven."""
from __future__ import annotations

import time

import pytest

from bench import harness
from bench.tests.smallcells import small_cell


def _run(cell, tmp_path, seed=3):
    result, checks = harness.run_cell(cell, seed, 0.5, False,
                                      time.perf_counter(), lambda _: None,
                                      out_dir=tmp_path)
    assert result["attempted"] > 0
    assert list(result)[-1] == "checks"
    return result


def _reader_fault(kind):
    from repro.kernels import ops
    orig = ops.hail_read_batch

    def broken(mins, keys, proj, bad, use_index, lohi, **kw):
        mask, out, frac = orig(mins, keys, proj, bad, use_index, lohi, **kw)
        if kind == "altered_answer":         # a value changed where made
            out = out.at[:, 0].add(1)
        elif kind == "half_batch_dropped":   # later half of the batch
            n_q = mask.shape[1]
            keep = (n_q + 1) // 2
            if n_q > 1:
                mask = mask.at[:, keep:].set(False)
        return mask, out, frac
    return ops, "hail_read_batch", broken


def _upload_fault(kind):
    from repro.core import index, upload
    upload._hail_pipeline.cache_clear()
    if kind == "state_unchanged":            # the sort step does nothing
        import jax.numpy as jnp
        return index, "sort_permutation", (
            lambda key, bad=None: jnp.arange(key.shape[0], dtype=jnp.int32))
    orig = index.build_root                  # a root directory altered
    return index, "build_root", (lambda k, p=1024: orig(k, p) + 1)


@pytest.mark.parametrize("name", ["uservisits.bob", "synthetic.scan"])
@pytest.mark.parametrize("fault", [None, "altered_answer",
                                   "half_batch_dropped"])
def test_query_cell_faults(name, fault, tmp_path, monkeypatch):
    cell = small_cell(name)
    if fault:
        monkeypatch.setattr(*_reader_fault(fault))
    result = _run(cell, tmp_path)
    assert result["correct"] is (fault is None), result["checks"]


@pytest.mark.parametrize("fault", [None, "state_unchanged",
                                   "altered_answer"])
def test_upload_cell_faults(fault, tmp_path, monkeypatch):
    from repro.core import upload
    cell = small_cell("uservisits.upload")
    if fault:
        monkeypatch.setattr(*_upload_fault(fault))
    try:
        result = _run(cell, tmp_path)
    finally:
        upload._hail_pipeline.cache_clear()
    assert result["correct"] is (fault is None), result["checks"]


@pytest.mark.parametrize("name", ["uservisits.bob", "synthetic.scan",
                                  "uservisits.upload"])
def test_control_is_not_correct(name, tmp_path):
    cell = small_cell(name)
    ctx = harness.Context(cell, 5, lambda _: None)
    state = cell.loop.setup(ctx)
    try:
        rec = cell.loop.window(state, 0.3)
        program, _ = cell.loop.check(state, rec)
        control, n_wrong = cell.loop.control(state, rec)
    finally:
        cell.loop.release(state)
    assert all(v <= lim for v, lim in program.values()), program
    assert n_wrong > 0 and any(v > program[k][1]
                               for k, v in control.items()), control
