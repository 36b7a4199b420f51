"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run can hold."""
from __future__ import annotations

import copy

from bench import harness


# The upload loop has no cell in BENCHMARK.json while the stall of its
# window is unexplained (PERF.md); its tests run it as this cell.
UPLOAD = {"name": "uservisits.upload", "config": "uservisits",
          "traffic": "upload", "chips": 1}


def small_cell(name: str):
    bench = harness.load_benchmark()
    if name == UPLOAD["name"]:
        bench["workloads"].append(UPLOAD)
    cell = harness.resolve(bench, name)
    cfg = copy.deepcopy(cell.config)
    traffic = copy.deepcopy(cell.traffic)
    cfg.update(rows_per_block=1024, partition_size=128)
    cfg["server"] = dict(cfg.get("server", {}), max_batch=2)
    if traffic["loop"] == "upload":
        cfg["n_blocks"] = 4
        traffic.update(chunk_blocks=2, resident=2)
    else:
        cfg["n_blocks"] = 2
        for t in traffic["templates"]:
            t["per_round"] = 1
    cell.config, cell.traffic = cfg, traffic
    return cell
