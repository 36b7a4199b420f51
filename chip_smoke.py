"""Smoke test of the HAIL serving path on a TPU: upload -> HailServer
submit/flush -> fused Pallas reader (compiled through Mosaic) -> rows on the
host, at deployment scale, every answer checked against a numpy reference.

    python3 chip_smoke.py              # one chip: served store + lazy phase
    python3 chip_smoke.py --chips 4    # the sharded flush over four chips

The store is UserVisits generated from ``--seed``: ``--blocks`` HDFS-sized
blocks of 2^19 rows (about 48 MB of text each), uploaded as three replicas
clustered on visitDate, sourceIP and adRevenue.  Timings printed here are
informational, not benchmark numbers.  The last line of standard output is
one JSON object naming the device; any failure exits non-zero before it.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ROWS = 2 ** 19                 # rows per block: one HDFS block of text
PARTITION = 1024               # rows per index partition (paper default)
SORT_KEYS = ("visitDate", "sourceIP", "adRevenue")
LAZY_BLOCKS = 4


class SmokeFailure(RuntimeError):
    pass


def _expect(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def _mib(n: int) -> str:
    return f"{n / 2**20:.1f} MiB"


def _memory(devices) -> str:
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(f"dev{d.id}: bytes_in_use={st.get('bytes_in_use')} "
                   f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")
    return "; ".join(out)


def count_compiles() -> collections.Counter:
    """Count XLA compiles, their seconds and persistent-cache hits/misses
    from JAX's monitoring events, so a run can show what the cache saved."""
    import jax
    counts = collections.Counter()

    def on_event(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            counts[event.rsplit("/", 1)[1]] += 1

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1
            counts["compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def make_data(n_blocks: int, rows: int, seed: int):
    """UserVisits columns, their ASCII blocks, and the bad-row mask read
    straight off the bytes (a row is bad when a digit position is not a
    digit) — independent of the parser under test."""
    from repro.core import schema as sc
    from repro.core.parse import format_rows
    t0 = time.perf_counter()
    cols = sc.gen_uservisits(n_blocks * rows, seed=seed)
    raw = format_rows(sc.USERVISITS, cols, bad_fraction=0.0005, seed=seed + 1)
    bad = np.zeros(len(raw), bool)
    for s in range(0, len(raw), rows):
        bad[s:s + rows] = ((raw[s:s + rows, :-1] - ord("0")) > 9).any(axis=1)
    print(f"data: {len(raw)} rows generated on the host in "
          f"{time.perf_counter() - t0:.3f} s, {int(bad.sum())} bad")
    return cols, raw.reshape(n_blocks, rows, -1), bad


def queries(cols: dict, bad: np.ndarray, shift: int):
    """Bob-Q1, Q4, Q5 and a point query on sourceIP (benchmarks/common.py),
    their ranges moved by ``shift`` steps so a later flush asks new ones."""
    from benchmarks.common import BOB_QUERIES
    from repro.core.query import HailQuery
    step = {"visitDate": 500, "adRevenue": 30000}
    out = {}
    for name in ("Bob-Q1", "Bob-Q4", "Bob-Q5", "Bob-Q2"):
        col, lo, hi, proj = BOB_QUERIES[name]
        if lo is None:                 # point query on an existing sourceIP
            good = np.flatnonzero(~bad)
            lo = hi = int(cols[col][good[(12345 + 7919 * shift) % len(good)]])
        else:
            lo, hi = lo + shift * step[col], hi + shift * step[col]
        out[name] = HailQuery(filter=(col, lo, hi), projection=proj)
    return out


def check_answer(name: str, query, result, cols: dict, bad: np.ndarray):
    """Row-set by __rowid__ and projected values against a numpy filter over
    the generated columns, bad rows left out."""
    from repro.core.schema import ROWID
    col, lo, hi = query.filter
    n = len(bad)
    want = np.flatnonzero((cols[col][:n] >= lo) & (cols[col][:n] <= hi)
                          & ~bad)
    order = np.argsort(result.rows[ROWID], kind="stable")
    got = result.rows[ROWID][order]
    _expect(result.n_rows == len(want) and np.array_equal(got, want),
            f"{name}: {result.n_rows} rows, reference has {len(want)}")
    for c in query.projection:
        _expect(np.array_equal(result.rows[c][order], cols[c][want]),
                f"{name}: column {c} differs from the reference")
    print(f"  {name} {col} in [{lo}, {hi}]: {len(want)} rows == reference "
          f"(batch of {result.batch_size}, {result.n_splits} splits)")


def serve(server, qs: dict, label: str):
    """One flush of every query; returns (tickets by name, wall seconds,
    reader retraces during the flush)."""
    from repro.kernels import ops
    tickets = {name: server.submit(q, tenant=name) for name, q in qs.items()}
    traces0 = sum(ops.reader_stats()["traces"].values())
    t0 = time.perf_counter()
    stats = server.flush()
    wall = time.perf_counter() - t0
    retraces = sum(ops.reader_stats()["traces"].values()) - traces0
    print(f"{label}: wall {wall:.3f} s, {stats.n_batches} batches "
          f"{stats.batch_sizes}, {stats.n_splits} fused dispatches, "
          f"{retraces} reader traces")
    for name, t in tickets.items():
        _expect(t.status == "done", f"{name}: ticket {t.status} ({t.error})")
    _expect(max(stats.batch_sizes) >= 2, f"{label}: no batch with Q > 1")
    return tickets, wall, retraces


def upload(raw, key_cols):
    from repro.core import schema as sc
    from repro.core import upload as up
    t0 = time.perf_counter()
    store, stats = up.hail_upload(sc.USERVISITS, raw, index_columns=key_cols,
                                  partition_size=PARTITION)
    wall = time.perf_counter() - t0
    print(f"upload {raw.shape[0]} blocks x {raw.shape[1]} rows "
          f"({_mib(raw.size)} ASCII, {_mib(store.nbytes)} PAX over "
          f"{len(store.replicas)} replicas): wall {wall:.3f} s "
          f"(device pipeline {stats.wall_s:.3f} s)")
    return store


def check_reader_program(rows: int):
    """The served reader's compiled program holds the Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    _expect(ops.interpret_mode() is False, "interpret mode is on")
    t0 = time.perf_counter()
    sds = jax.ShapeDtypeStruct
    text = ops._hail_read_batch_jit.lower(
        sds((1, rows // PARTITION), jnp.int32), sds((1, rows), jnp.int32),
        sds((1, 4, rows), jnp.int32), sds((1, rows), jnp.bool_),
        sds((1,), jnp.int32), sds((2, 2), jnp.int32),
        reader=ops.hail_read_batch, partition_size=PARTITION,
        interpret=ops.interpret_mode()).compile().as_text()
    _expect("tpu_custom_call" in text, "no tpu_custom_call in the reader")
    print("reader program: tpu_custom_call present, interpret mode off "
          f"(lowered and compiled in {time.perf_counter() - t0:.3f} s)")


def one_chip(n_blocks: int, rows: int, seed: int, devices):
    from repro.core.mapreduce import AdaptiveConfig
    from repro.runtime.jobserver import HailServer, ServerConfig

    cols, raw, bad = make_data(n_blocks, rows, seed)
    store = upload(raw, SORT_KEYS)
    print("after upload:", _memory(devices))
    server = HailServer(store, ServerConfig())
    for shift, label in ((0, "cold flush"), (1, "warm flush")):
        qs = queries(cols, bad, shift)
        tickets, _, retraces = serve(server, qs, label)
        for name, t in tickets.items():
            check_answer(name, qs[name], t.result, cols, bad)
        if shift:
            _expect(retraces == 0, f"warm flush retraced {retraces} times")
    check_reader_program(rows)
    print("after flushes:", _memory(devices))
    del server, store, tickets
    gc.collect()
    print("store freed:", _memory(devices))

    # lazy upload: no index at upload time; adaptive flushes sort + index
    # blocks on the device and commit them while serving
    n = LAZY_BLOCKS * rows
    lazy = upload(raw[:LAZY_BLOCKS], ())
    server = HailServer(lazy, ServerConfig(
        adaptive=AdaptiveConfig(offer_rate=0.5)))
    built = 0
    for shift in (0, 1):
        qs = queries(cols, bad[:n], shift)
        tickets = {name: server.submit(q, tenant=name)
                   for name, q in qs.items()}
        t0 = time.perf_counter()
        stats = server.flush()
        built += stats.blocks_indexed
        print(f"adaptive flush {shift}: wall "
              f"{time.perf_counter() - t0:.3f} s, {stats.blocks_indexed} "
              f"blocks sorted + indexed, {stats.n_splits} fused dispatches")
        for name, t in tickets.items():
            _expect(t.status == "done", f"{name}: ticket {t.status}")
            check_answer(name, qs[name], t.result, cols, bad[:n])
    _expect(built > 0, "the adaptive flushes indexed nothing")


def four_chips(n_blocks: int, rows: int, seed: int, devices):
    """The sharded flush over a ("data",) mesh, row-sets checked against the
    single-device serial path and the numpy reference."""
    from repro.launch.mesh import make_mesh
    from repro.runtime.jobserver import HailServer, ServerConfig

    mesh = make_mesh((len(devices),), ("data",))
    cols, raw, bad = make_data(n_blocks, rows, seed)
    store = upload(raw, SORT_KEYS)
    sharded = HailServer(store, ServerConfig(mesh=mesh))
    serial = HailServer(store, ServerConfig(result_cache=False))
    for shift, label in ((0, "cold"), (1, "warm")):
        qs = queries(cols, bad, shift)
        got, _, retraces = serve(sharded, qs, f"sharded {label} flush")
        if shift:
            _expect(retraces == 0, f"sharded warm flush retraced {retraces}")
        want, _, _ = serve(serial, qs, f"serial {label} flush")
        for name in qs:
            check_answer(name, qs[name], got[name].result, cols, bad)
            for c, v in want[name].result.rows.items():
                _expect(np.array_equal(np.sort(v),
                                       np.sort(got[name].result.rows[c])),
                        f"{name}: sharded {c} differs from the serial path")
        print(f"  sharded {label} answers == serial path")
        print("  per device:", _memory(devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=32,
                    help="HDFS-sized blocks of 2^19 rows (default 32)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded flush over four chips")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: run from a checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import place_compile_cache

    print(f"device: {dev.device_kind} x {len(devices)} ({dev.platform})")
    print("compile cache:", place_compile_cache())
    compiles = count_compiles()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.blocks, ROWS, args.seed, devices)
        else:
            one_chip(args.blocks, ROWS, args.seed, devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compiles: {compiles['compiles']} programs in "
          f"{compiles['compile_s']:.3f} s, persistent cache "
          f"{compiles['cache_hits']} hits / {compiles['cache_misses']} misses")
    print(f"total wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
