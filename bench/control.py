"""Readings that set the limits of ``correct``: the program's and the
control's, for one cell at its own size, over several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds 10 --seeds 1 2 3

For each seed: the cell's set-up, a short window at the cell's own load,
the comparison of the program's answers with the reference, and the same
comparison applied to the control (the reference with one stated guarantee
broken, put in the program's place: ``control`` of the cell's loop).  The
benchmark's own runs do not run the control.  One JSON line per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import place_compile_cache
    place_compile_cache()
    for seed in args.seeds:
        ctx = harness.Context(cell, seed, print)
        t = time.perf_counter()
        state = cell.loop.setup(ctx)
        rec = cell.loop.window(state, args.seconds)
        program, n_wrong = cell.loop.check(state, rec)
        t_ctrl = time.perf_counter()
        ctrl, c_wrong = cell.loop.control(state, rec)
        cell.loop.release(state)
        del rec, state
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program": {k: v for k, (v, _) in program.items()},
            "program_wrong": n_wrong, "control": ctrl,
            "control_wrong": c_wrong,
            "control_s": time.perf_counter() - t_ctrl,
            "seed_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
