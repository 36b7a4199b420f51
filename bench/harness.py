"""The benchmark harness, driven by ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
harness finds everything by those names:

* ``configs[].file``              the configuration (sizes, replicas,
                                   server settings, guarantees);
* ``bench/traffic/<traffic>.json`` the mix, whose ``loop`` names
* ``bench/loops/<loop>.py``        the loop: ``setup``, ``window``,
                                   ``check``, ``release``;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric, whose
                                   ``read(rec)`` returns a number or None.

A later change adds a configuration, a mix, a loop or a metric by adding
files and entries.  ``run_cell`` runs one cell and returns the result line
and the numbers compared against their limits.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Callable

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"


class BenchError(RuntimeError):
    """A cell, file or name that does not resolve."""


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    loop: Any
    end_to_end: list               # metric entries this cell reports
    per_layer: list                # (entry, reader function)


def _reports(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", (cell,))


def resolve(bench: dict, workload: str, root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{workload}: unknown config {w['config']!r}")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    tpath = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not tpath.is_file():
        raise BenchError(f"{workload}: no traffic file {tpath}")
    traffic = json.loads(tpath.read_text())
    loop = load_module(root / "bench" / "loops" / f"{traffic['loop']}.py",
                       f"bench_loop_{traffic['loop']}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    per_layer = []
    for m in bench["per_layer"]:
        if _reports(m, workload):
            mod = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                              f"bench_metric_{m['name'].replace('.', '_')}")
            per_layer.append((m, mod.read))
    return Cell(workload, int(w["chips"]), config, traffic, loop, e2e,
                per_layer)


def program_schema(cfg: dict):
    """The program's schema object for a configuration, checked against the
    configuration's own column list (names, order and text widths)."""
    from repro.core import schema as sc
    schema = getattr(sc, cfg["program_schema"])
    want = [(c["name"], int(c["ascii_width"])) for c in cfg["columns"]]
    got = [(c.name, c.ascii_width) for c in schema.columns]
    if want != got:
        raise BenchError(f"{cfg['name']}: columns {want} differ from the "
                         f"program's {cfg['program_schema']} {got}")
    return schema


@dataclasses.dataclass
class Context:
    """What a loop gets: the cell, the seed and where to log."""
    cell: Cell
    seed: int
    log: Callable[[str], None]


def profile_options():
    """Device activity in full; on the host level 1 only (annotations and
    the runtime's own level-1 events, no Python tracer), which keeps a
    51-second window of a query cell to about 70 MB of trace."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _profile(out_dir: pathlib.Path):
    import jax
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    jax.profiler.start_trace(str(trace_dir),
                             profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench:anchor"):
        anchor = time.perf_counter()
    return trace_dir, anchor


def _obs_spans(events, tracer_t0: float, anchor_pc: float,
               anchor_ns: float) -> list:
    """The program's obs spans (B/E pairs and X slices) as (name, start,
    end) on the profiler's clock."""
    def to_ns(ts_us):
        return anchor_ns + (tracer_t0 + ts_us / 1e6 - anchor_pc) * 1e9
    out, open_ = [], {}
    for ev in events:
        ph = ev.get("ph")
        if ph == "X":
            s = to_ns(ev["ts"])
            out.append((ev["name"], s, s + ev["dur"] * 1e3))
        elif ph == "B":
            open_.setdefault((ev["tid"], ev["name"]), []).append(ev["ts"])
        elif ph == "E":
            stack = open_.get((ev["tid"], ev["name"]))
            if stack:
                out.append((ev["name"], to_ns(stack.pop()), to_ns(ev["ts"])))
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = print,
             out_dir: pathlib.Path | None = None) -> tuple[dict, dict]:
    """Set up, measure for ``seconds``, check.  -> (result line, checks),
    where checks maps each compared number to (value, limit)."""
    import jax
    from bench.stats import compile_counter
    from bench import tracereduce

    counter = compile_counter()
    state = cell.loop.setup(Context(cell, seed, log))
    setup_s = time.perf_counter() - t_start
    before = counter.snapshot()
    tracer = None
    if trace:
        from repro.obs import trace as obs_trace
        trace_dir, anchor_pc = _profile(out_dir or OUT / cell.name)
        tracer = obs_trace.install()
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            rec = cell.loop.window(state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            obs_trace.uninstall()
    after = counter.snapshot()
    n_compiles = after["compiles"] - before["compiles"]
    log(f"window: {rec['window_s']:.3f} s, {rec['attempted']} attempted, "
        f"compiles inside the window: {n_compiles} "
        f"(persistent cache {after['cache_hits'] - before['cache_hits']} "
        f"hits / {after['cache_misses'] - before['cache_misses']} misses)")
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    log(f"device: {device['kind']} x {device['count']} "
        f"({device['platform']}), peak {peak} bytes "
        f"({stats.get('bytes_limit')} limit)")
    rec["device_kind"] = device["kind"]
    if trace:
        rec["obs_events"] = tracer.events
        t_trace = time.perf_counter()
        xp = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
        planes = tracereduce.load(str(xp))
        anchor_ns = min(s for n, s, _ in tracereduce.host_events(planes)
                        if n == "bench:anchor")
        extra = _obs_spans(tracer.events, tracer.t0, anchor_pc, anchor_ns)
        rec["trace"] = tracereduce.reduce(planes, extra)
        log(f"trace: {xp.stat().st_size} bytes, read in "
            f"{time.perf_counter() - t_trace:.3f} s")
        device["busy_s"] = rec["trace"].busy_s
        device["window_s"] = rec["trace"].window_s
    t_check = time.perf_counter()
    checks, n_wrong = cell.loop.check(state, rec)
    cell.loop.release(state)
    log(f"reference check: {time.perf_counter() - t_check:.3f} s")
    correct = all(v <= lim for v, lim in checks.values())
    if trace:
        metrics = {}
        for entry, read in cell.per_layer:
            v = read(rec)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in rec["e2e"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result = {"correct": bool(correct), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"] + n_wrong), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = rec["trace"].breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks
