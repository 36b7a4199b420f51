"""Pallas kernels: interpret-mode correctness + us/call vs jnp oracle.
(Interpret mode executes the kernel body in Python — timings demonstrate the
harness, not TPU performance; the TPU path flips interpret=False.)

Also records the fused split-reader's DISPATCH and RECOMPILE counts (plus
per-query latency over distinct ranges) to BENCH_kernels.json — the
regression guard for the one-dispatch-per-split / zero-per-query-recompile
properties (see EXPERIMENTS.md)."""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.index_search import index_search
from repro.kernels.pax_scan import pax_scan

KEY = jax.random.PRNGKey(0)

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_kernels.json")


def reader_dispatch_stats(n_queries: int = 10) -> dict:
    """Run n_queries distinct (lo, hi) ranges through the fused reader on a
    small HAIL store; count dispatches, retraces, and per-query latency."""
    from benchmarks.common import uservisits_raw
    from repro.core import query as q
    from repro.core import schema as sc
    from repro.core import upload as up
    from repro.kernels import ops

    _, raw = uservisits_raw(blocks=8, rows=4096)
    store, _ = up.hail_upload(sc.USERVISITS, raw,
                              ["visitDate", "sourceIP", "adRevenue"],
                              n_nodes=4)
    qp = q.plan(store, q.HailQuery(filter=("visitDate", 0, 1),
                                   projection=("sourceIP",)))
    ranges = [(7305 + 13 * i, 7670 + 29 * i) for i in range(n_queries)]
    ops.reset_stats()
    lat_us = []
    for lo, hi in ranges:
        query = q.HailQuery(filter=("visitDate", lo, hi),
                            projection=("sourceIP",))
        t0 = time.perf_counter()
        res = q.read_hail_kernels(store, query, qp)
        jax.block_until_ready(res.mask)
        lat_us.append((time.perf_counter() - t0) * 1e6)
    stats = ops.reader_stats()
    return {
        "n_queries": n_queries,
        "n_splits_per_query": 1,
        "dispatches": stats["dispatches"].get("hail_read", 0),
        "recompiles": stats["traces"].get("hail_read", 0),
        "recompiles_after_first": max(
            stats["traces"].get("hail_read", 0) - 1, 0),
        "per_query_latency_us": [round(u, 1) for u in lat_us],
        "first_query_us": round(lat_us[0], 1),
        "steady_state_us": round(
            sorted(lat_us[1:])[len(lat_us[1:]) // 2], 1),
    }


def run():
    rows = []
    mins = jnp.sort(jax.random.randint(KEY, (64, 64), 0, 1 << 20,
                                       dtype=jnp.int32), axis=1)
    t, _ = timed(lambda: index_search(mins, 1000, 100000))
    tr, _ = timed(lambda: ref.index_search(mins, 1000, 100000))
    rows.append(("kernel_index_search_64x64", t * 1e6, f"ref_us={tr * 1e6:.0f}"))

    kc = jax.random.randint(KEY, (8192,), 0, 1 << 20, dtype=jnp.int32)
    pj = jax.random.randint(KEY, (8192, 4), 0, 99, dtype=jnp.int32)
    t, _ = timed(lambda: pax_scan(kc, pj, 0, 1 << 18))
    tr, _ = timed(lambda: ref.pax_scan(kc, pj, 0, 1 << 18))
    rows.append(("kernel_pax_scan_8192x4", t * 1e6, f"ref_us={tr * 1e6:.0f}"))

    q = jax.random.normal(KEY, (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 256, 2, 64))
    t, _ = timed(lambda: flash_attention(q, k, v, block_q=128, block_k=128))
    tr, _ = timed(lambda: ref.attention(q, k, v))
    rows.append(("kernel_flash_attn_256", t * 1e6, f"ref_us={tr * 1e6:.0f}"))

    from repro.kernels.selective_scan import selective_scan
    ks = [jax.random.fold_in(KEY, 10 + i) for i in range(5)]
    delta = jax.nn.softplus(jax.random.normal(ks[0], (1, 64, 32)))
    x2 = jax.random.normal(ks[1], (1, 64, 32))
    b2 = jax.random.normal(ks[2], (1, 64, 8))
    c2 = jax.random.normal(ks[3], (1, 64, 8))
    a2 = -jnp.exp(jax.random.normal(ks[4], (32, 8)) * 0.3)
    t, _ = timed(lambda: selective_scan(delta, x2, b2, c2, a2,
                                        chunk=16, d_block=16))
    tr, _ = timed(lambda: ref.selective_scan(delta, x2, b2, c2, a2))
    rows.append(("kernel_selective_scan_64x32", t * 1e6,
                 f"ref_us={tr * 1e6:.0f}"))

    # fused split reader: dispatch/recompile regression guard -> JSON
    # (merge so bench_query's query_job_latency_us keys survive either order)
    ds = reader_dispatch_stats()
    blob = {}
    if os.path.exists(JSON_PATH):
        with open(JSON_PATH) as f:
            blob = json.load(f)
    blob.update(ds)
    with open(JSON_PATH, "w") as f:
        json.dump(blob, f, indent=1)
    rows.append(("kernel_hail_read_dispatches", ds["steady_state_us"],
                 f"dispatches={ds['dispatches']};"
                 f"recompiles_after_first={ds['recompiles_after_first']};"
                 f"json={os.path.basename(JSON_PATH)}"))
    return rows
