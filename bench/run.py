"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device`` and, traced, ``breakdown``; its last key, ``checks``, holds each
number compared with the reference beside its limit, which also end
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 2.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    cell = harness.resolve(harness.load_benchmark(), args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.compile_cache import place_compile_cache
    place_compile_cache()

    def log(msg):
        print(msg, flush=True)

    log(f"device: {devices[0].device_kind} x {len(devices)} "
        f"({devices[0].platform}); cell {cell.name}, seed {args.seed}")
    result, checks = harness.run_cell(cell, args.seed, args.seconds,
                                      bool(args.trace), T_START, log)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
