"""Runs the four-chip cell ``uservisits4.bob`` at a small size on four
virtual CPU devices, and prints one JSON line per reading.

JAX fixes its device count when it starts, and the test process must see
one device, so ``test_bench_cluster.py`` runs this as a subprocess:

    python bench/tests/cluster_worker.py

Readings: ``correct`` of a whole run (``harness.run_cell``); then, on one
set-up, ``correct`` of a window in which one chip's reader output is
altered, and of one in which one chip's splits are dropped; and the
``chip_balance_pct`` and ``chips_in_flight`` readers on a traced window.
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tests.smallcells import small_cell  # noqa: E402

CELL = "uservisits4.bob"
SEED = 2 ** 31 + 17


def emit(**kw):
    print(json.dumps(kw), flush=True)


def correct(checks) -> bool:
    return all(v <= lim for v, lim in checks.values())


def cluster_cell():
    cell = small_cell(CELL)
    cell.config["n_blocks"] = 8                # two home blocks a chip
    return cell


def altered_on_chip(chip):
    """The reader's first projected column plus one, on one chip."""
    from repro.kernels import ops
    orig = ops.hail_read_batch_split

    def altered(*args, **kw):
        out = orig(*args, **kw)
        if out.cols[0].devices() == {chip}:
            out = out._replace(cols=(out.cols[0] + 1,) + out.cols[1:])
        return out
    return ops, "hail_read_batch_split", altered


def dropped_on_chip(chip: int):
    """No split on one chip is issued: each is taken as live on nothing."""
    from repro.runtime.jobserver import HailServer
    orig = HailServer._live_members

    def dropped(self, qplan, sp, queries):
        if self.store.chip_of(sp.node) == chip:
            return []
        return orig(self, qplan, sp, queries)
    return HailServer, "_live_members", dropped


def main():
    import jax
    assert jax.device_count() == 4, jax.device_count()
    cell = cluster_cell()
    result, checks = harness.run_cell(cell, SEED, 0.5, False,
                                      time.perf_counter(), lambda _: None)
    emit(reading="run", correct=result["correct"],
         attempted=result["attempted"], checks=result["checks"])

    state = cell.loop.setup(harness.Context(cell, SEED + 1, lambda _: None))
    try:
        for name, (owner, attr, fault) in (
                ("altered_on_one_chip", altered_on_chip(jax.devices()[1])),
                ("one_chip_dropped", dropped_on_chip(2))):
            orig = getattr(owner, attr)
            setattr(owner, attr, fault)
            try:
                rec = cell.loop.window(state, 0.5)
            finally:
                setattr(owner, attr, orig)
            program, _ = cell.loop.check(state, rec)
            emit(reading=name, correct=correct(program),
                 checks={k: v for k, (v, _) in program.items()})

        from repro.obs import trace as obs_trace
        tracer = obs_trace.install()
        try:
            rec = cell.loop.window(state, 0.5)
        finally:
            obs_trace.uninstall()
        rec["obs_events"] = tracer.events
        metrics = {}
        for entry, read in cell.per_layer:
            if entry["name"] in ("chip_balance_pct", "chips_in_flight"):
                metrics[entry["name"]] = read(rec)
        emit(reading="metrics", metrics=metrics,
             chip_blocks=rec["flushes"][0][0].chip_blocks)
    finally:
        cell.loop.release(state)


if __name__ == "__main__":
    main()
