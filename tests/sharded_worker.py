"""Subprocess worker for the 8-device sharded-scan property test.

Forced host device count MUST be set before any jax import (conftest forbids
XLA_FLAGS in the test process itself, so this runs via subprocess).  The
worker randomizes interleaved commits, demotions, quarantines and
re-replications between sharded flushes and checks every answered row-set
against the uncached oracle computed from the generating columns.  Exits
non-zero (assertion) on any divergence; prints PASS lines the test asserts.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"     # never contend for an accelerator

import math  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import mapreduce as mr  # noqa: E402
from repro.core import query as q  # noqa: E402
from repro.core import schema as sc  # noqa: E402
from repro.core import upload as up  # noqa: E402
from repro.core.parse import format_rows, parse_block  # noqa: E402
from repro.core.schema import ROWID  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.runtime.jobserver import HailServer, ServerConfig  # noqa: E402

ROWS, BLOCKS, PART, NODES = 256, 12, 64, 6
N_DEV = 8


def build_store():
    cols = sc.gen_uservisits(ROWS * BLOCKS, seed=3)
    raw = format_rows(sc.USERVISITS, cols, bad_fraction=0.004)
    store, _ = up.hail_upload(
        sc.USERVISITS, raw.reshape(BLOCKS, ROWS, -1),
        ["visitDate", "sourceIP"], partition_size=PART, n_nodes=NODES)
    import jax
    bad = np.asarray(jax.jit(jax.vmap(
        lambda r: parse_block(sc.USERVISITS, r)[1]))(
            raw.reshape(BLOCKS, ROWS, -1))).reshape(-1)
    return store, cols, bad


def oracle_rowids(cols, bad, col, lo, hi):
    keep = (cols[col] >= lo) & (cols[col] <= hi) & ~bad
    return np.nonzero(keep)[0]


def main():
    import jax
    assert jax.device_count() == N_DEV, jax.device_count()
    mesh = make_mesh((N_DEV,), ("data",))
    store, cols, bad = build_store()
    rng = np.random.default_rng(0)
    qcols = ["visitDate", "sourceIP", "adRevenue"]

    # --- dispatch-count model: per-device fused dispatches = ceil(S/D) ----
    query = q.HailQuery(filter=("visitDate", 7305, 7670),
                        projection=("sourceIP",))
    with ops.stats_scope() as stats:
        job = mr.run_job(store, query, mesh=mesh)
    s = len(job.split_s)
    waves = stats.dispatches["hail_read_sharded_waves"]
    assert waves == math.ceil(s / N_DEV), (waves, s)
    assert stats.dispatches["hail_read_sharded_splits"] == s
    serial = mr.run_job(store, query)
    assert job.results["n_rows"] == serial.results["n_rows"]
    assert job.bytes_read == serial.bytes_read, \
        (job.bytes_read, serial.bytes_read)
    print(f"PASS dispatch-model waves={waves} splits={s}")

    # --- randomized interleaving: flushes vs the uncached oracle ----------
    server = HailServer(store, ServerConfig(
        mesh=mesh, result_cache=False,
        adaptive=mr.AdaptiveConfig(offer_rate=0.5)))
    checked = 0
    for round_i in range(6):
        # mutate: quarantine a random healthy copy / demote / re-replicate
        op = rng.integers(0, 4)
        if op == 0:
            live = store.live_replica_ids()
            rid = int(rng.choice(live))
            b = int(rng.integers(0, store.n_blocks))
            if len(store.alive_replica_ids(b)) > 1 and \
                    not store.is_quarantined(rid, b):
                store.quarantine_block(rid, b)
        elif op == 1:
            claimed = [i for i in store.live_replica_ids()
                       if store.replicas[i].sort_key is not None]
            if len(claimed) > 1:
                store.demote_replica(int(rng.choice(claimed)))
        elif op == 2 and len(store.live_replica_ids()) < 4:
            store.add_replica()
        elif op == 3 and len(store.live_replica_ids()) > 2:
            rid = store.live_replica_ids()[-1]
            try:
                store.decommission_replica(rid)
            except ValueError:
                pass                 # a block's last healthy copy: keep it
        # submit a compatible batch + a singleton on another column
        col = qcols[int(rng.integers(0, len(qcols)))]
        vals = np.sort(cols[col])
        tickets = []
        for _ in range(3):
            lo, hi = sorted(int(vals[i]) for i in
                            rng.integers(0, len(vals), size=2))
            tk = server.submit(q.HailQuery(filter=(col, lo, hi),
                                           projection=("adRevenue",)))
            tickets.append((tk, col, lo, hi))
        fail_at = 0.5 if round_i == 3 else None    # mid-flush failover
        server.flush(fail_node_at=fail_at)
        for tk, tcol, lo, hi in tickets:
            assert tk.status == "done", tk.error
            got = np.sort(tk.result.rows[ROWID])
            want = oracle_rowids(cols, bad, tcol, lo, hi)
            assert got.shape == want.shape and (got == want).all(), \
                (round_i, tcol, lo, hi, got.shape, want.shape)
            checked += 1
    print(f"PASS oracle-equality queries={checked}")


if __name__ == "__main__":
    main()
