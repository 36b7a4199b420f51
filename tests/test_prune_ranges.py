"""Split pruning from the store's host key ranges.

Each replica keeps, per indexed block, its root minimum and last good
sorted key on the host (``Replica.key_range``), set where the store writes
the block.  ``HailServer._live_members`` prunes from that table alone.
These tests hold the table to a host oracle (the min and max good key of
each block's rows) and the live sets to the rule as it read the device
(a numpy oracle over the device arrays), in every store state the store's
writes reach: eager upload, blocks with every row bad, lazy upload with
commits, repair, demotion then re-claim, and an added replica.  Pruning
makes no device read, and a corrupted root directory can no longer prune
a live split.
"""
import jax
import numpy as np
import pytest

from repro.core import mapreduce as mr
from repro.core import query as q
from repro.core import schema as sc
from repro.core import upload as up
from repro.core.fault import FaultInjector
from repro.core.parse import parse_block
from repro.core.schema import ROWID
from repro.core.splitting import Split, hail_splits
from repro.obs import trace as obs_trace
from repro.runtime import jobserver as js

from conftest import BLOCKS, PART, ROWS

KEYS = ["visitDate", "sourceIP", "adRevenue"]


def _upload(raw, keys=KEYS):
    store, _ = up.hail_upload(sc.USERVISITS, raw, keys, partition_size=PART,
                              n_nodes=6)
    return store


def _bad_rows(raw):
    return np.asarray(jax.jit(jax.vmap(
        lambda r: parse_block(sc.USERVISITS, r)[1]))(raw)).reshape(-1)


@pytest.fixture()
def served_store(uservisits_raw):
    """A fresh eagerly indexed store per test (tests rewrite it)."""
    return _upload(uservisits_raw[1])


@pytest.fixture()
def table(uservisits_raw):
    """(columns, raw text, bad-row mask) with block 2's rows all bad."""
    cols, raw = uservisits_raw
    raw = raw.copy()
    raw[2, :, 0] = ord("x")
    return cols, raw, _bad_rows(raw)


def _oracle_ranges(cols, bad, key):
    """(BLOCKS, 2) min and max good ``key`` of each block; -1 rows where a
    block has no good row."""
    out = np.full((BLOCKS, 2), -1, np.int64)
    for b in range(BLOCKS):
        rows = slice(b * ROWS, (b + 1) * ROWS)
        good = cols[key][rows][~bad[rows]]
        if len(good):
            out[b] = good.min(), good.max()
    return out


def _assert_table(store, cols, bad):
    """Every indexed block's host key range equals the host oracle."""
    for rep in store.replicas:
        if rep.retired or rep.sort_key is None:
            continue
        want = _oracle_ranges(cols, bad, rep.sort_key)
        for b in np.flatnonzero(rep.indexed):
            if want[b, 0] >= 0:
                assert tuple(rep.key_range[b]) == tuple(want[b]), (
                    rep.sort_key, b)


def _device_rule(store, qplan, block_ids, queries):
    """The pruning rule as it read the device: per index-scanned block, the
    root minimum and the last good sorted key fetched from the device
    arrays; any full-scan block keeps the whole batch live."""
    if any(not qplan.index_scan[b] for b in block_ids):
        return list(range(len(queries)))
    col = queries[0].filter_col
    bad = np.asarray(store.bad_counts)
    live = set()
    for b in block_ids:
        rep = store.replicas[int(qplan.replica_for_block[b])]
        n_good = store.rows_per_block - int(bad[b])
        if n_good <= 0:
            continue
        kmin = int(np.asarray(rep.mins)[b, 0])
        kmax = int(np.asarray(rep.cols[col])[b, n_good - 1])
        live |= {i for i, x in enumerate(queries)
                 if x.filter[2] >= kmin and x.filter[1] <= kmax}
    return sorted(live)


def _random_batch(rng, col, ranges, n):
    """``n`` queries on ``col``: half straddle an edge of some block's key
    range by a few keys (live or dead by one key), half are random."""
    lo_d, hi_d = int(ranges[:, 0].min()), int(ranges[:, 1].max())
    out = []
    for _ in range(n):
        if rng.random() < 0.5:
            b = int(rng.integers(len(ranges)))
            d = int(rng.integers(-3, 4))
            w = int(rng.integers(0, 50))
            if rng.random() < 0.5:
                lo = int(ranges[b, 1]) + d
                hi = lo + w
            else:
                hi = int(ranges[b, 0]) + d
                lo = hi - w
        else:
            lo = int(rng.integers(lo_d - 100, hi_d + 100))
            hi = lo + int(rng.integers(0, (hi_d - lo_d) // 4))
        out.append(q.HailQuery(filter=(col, lo, hi), projection=(ROWID,)))
    return out


def _assert_live_sets(store, cols, bad, seed, n_batches=12):
    """For random batches on every indexed key, over the plan's own splits
    and random block subsets, ``_live_members`` gives the device rule's
    live set."""
    server = js.HailServer(store, js.ServerConfig(result_cache=False))
    rng = np.random.default_rng(seed)
    compared = 0
    for rep in store.replicas:
        if rep.retired or rep.sort_key is None:
            continue
        col = rep.sort_key
        ranges = _oracle_ranges(cols, bad, col)
        ranges = ranges[ranges[:, 0] >= 0]
        qplan = q.plan(store, q.HailQuery(filter=(col, 0, 0),
                                          projection=(ROWID,)))
        splits = [tuple(s.block_ids) for s in hail_splits(store, qplan, 2)]
        for _ in range(4):
            k = int(rng.integers(1, BLOCKS + 1))
            splits.append(tuple(int(b) for b in
                                rng.choice(BLOCKS, k, replace=False)))
        for _ in range(n_batches):
            batch = _random_batch(rng, col, ranges,
                                  int(rng.integers(1, 9)))
            for ids in splits:
                sp = Split(node=0, block_ids=ids, index_scan=True)
                assert (server._live_members(qplan, sp, batch)
                        == _device_rule(store, qplan, ids, batch)), (col,
                                                                     ids)
                compared += 1
    assert compared > 0


def test_all_bad_block_keeps_its_meaning(table):
    cols, raw, bad = table
    store = _upload(raw)
    assert int(store.bad_counts[2]) == ROWS
    assert isinstance(store.bad_counts, np.ndarray)
    _assert_table(store, cols, bad)
    _assert_live_sets(store, cols, bad, seed=0)
    # a split of only the all-bad block is dead for every query
    qplan = q.plan(store, q.HailQuery(filter=("visitDate", 0, 0),
                                      projection=(ROWID,)))
    server = js.HailServer(store)
    wide = [q.HailQuery(filter=("visitDate", 0, 1 << 30),
                        projection=(ROWID,))]
    assert server._live_members(
        qplan, Split(node=0, block_ids=(2,), index_scan=True), wide) == []


def test_lazy_upload_fills_as_commits_land(uservisits_raw, oracle_rows):
    cols, bad = oracle_rows
    _, raw = uservisits_raw
    store, _ = up.hail_upload(sc.USERVISITS, raw, index_columns=(),
                              partition_size=PART, n_nodes=6, replication=3)
    assert all(not r.key_range.any() for r in store.replicas)
    assert mr._build_block_indexes(store, 0, [0, 2], "visitDate",
                                   partition_size=PART) == 2
    assert mr._build_block_indexes(store, 1, [1], "adRevenue",
                                   partition_size=PART) == 1
    _assert_table(store, cols, bad)
    _assert_live_sets(store, cols, bad, seed=1)
    # a later commit fills the rest of the replica's table
    mr._build_block_indexes(store, 0, [1, 3], "visitDate",
                            partition_size=PART)
    assert store.replicas[0].indexed.all()
    _assert_table(store, cols, bad)
    _assert_live_sets(store, cols, bad, seed=2)


def test_repair_rewrites_the_repaired_blocks(served_store, oracle_rows):
    cols, bad = oracle_rows
    before = served_store.replicas[0].key_range.copy()
    FaultInjector(served_store, seed=5).corrupt_column(0, 1, "visitDate")
    served_store.quarantine_block(0, 1)
    served_store.replicas[0].key_range[1] = (0, 0)     # must be rewritten
    assert served_store.repair_blocks().blocks_repaired == 1
    np.testing.assert_array_equal(served_store.replicas[0].key_range, before)
    _assert_table(served_store, cols, bad)
    _assert_live_sets(served_store, cols, bad, seed=3)


def test_demote_then_reclaim(served_store, oracle_rows):
    cols, bad = oracle_rows
    served_store.demote_replica(0)
    assert not served_store.replicas[0].key_range.any()
    _assert_live_sets(served_store, cols, bad, seed=4)
    # re-claim the replica for another key, block by block
    mr._build_block_indexes(served_store, 0, [3, 1], "duration",
                            partition_size=PART)
    assert served_store.replicas[0].sort_key == "duration"
    _assert_table(served_store, cols, bad)
    _assert_live_sets(served_store, cols, bad, seed=5)


def test_added_replica_claimed(served_store, oracle_rows):
    cols, bad = oracle_rows
    rid = served_store.add_replica(n_nodes=6)
    assert not served_store.replicas[rid].key_range.any()
    mr._build_block_indexes(served_store, rid, [0, 1, 2], "searchWord",
                            partition_size=PART)
    _assert_table(served_store, cols, bad)
    _assert_live_sets(served_store, cols, bad, seed=6)
    served_store.decommission_replica(rid)
    assert served_store.replicas[rid].key_range is None
    _assert_live_sets(served_store, cols, bad, seed=7)


def test_pruning_makes_no_device_read(served_store, oracle_rows):
    """After a warm-up flush, every ``_live_members`` call of a flush runs
    with transfers refused.  On a TPU the device-to-host guard refuses a
    read of a device scalar; on the CPU device memory is host memory, so
    the guard refuses every direction, which also catches the index that
    an eager slice of a device array uploads."""
    cols, bad = oracle_rows
    server = js.HailServer(served_store, js.ServerConfig(result_cache=False))
    for lo in (7100, 8200, 9300):
        server.submit(q.HailQuery(filter=("visitDate", lo, lo + 40),
                                  projection=("sourceIP",)))
    server.flush()
    orig, calls = server._live_members, []

    def guarded(*args, **kw):
        with jax.transfer_guard("disallow"):
            out = orig(*args, **kw)
        calls.append(out)
        return out
    server._live_members = guarded
    queries = [q.HailQuery(filter=("visitDate", lo, hi),
                           projection=("sourceIP",))
               for lo, hi in ((7305, 7670), (7, 7), (11990, 20000))]
    tickets = [server.submit(x) for x in queries]
    server.flush()
    assert calls and all(c == [0, 2] for c in calls)   # (7, 7) pruned
    for x, t in zip(queries, tickets):
        _, lo, hi = x.filter
        want = np.flatnonzero((cols["visitDate"] >= lo)
                              & (cols["visitDate"] <= hi) & ~bad)
        assert t.status == "done"
        np.testing.assert_array_equal(np.sort(t.result.rows[ROWID]), want)


def test_prune_span_arguments(served_store):
    """The ``prune`` span carries the blocks whose range was compared and
    whether the split is dead; a batch that misses every block is dead on
    every split."""
    server = js.HailServer(served_store, js.ServerConfig(result_cache=False))
    for lo, hi in ((7, 7), (0, 100)):
        server.submit(q.HailQuery(filter=("visitDate", lo, hi),
                                  projection=("sourceIP",)))
    tracer = obs_trace.install()
    try:
        server.flush()
        server.submit(q.HailQuery(filter=("visitDate", 7305, 7670),
                                  projection=("sourceIP",)))
        server.flush()
    finally:
        obs_trace.uninstall()
    prunes = [ev["args"] for ev in tracer.events
              if ev.get("name") == "prune" and ev["ph"] == "B"]
    assert len(prunes) > 0 and len(prunes) % 2 == 0
    dead, live = prunes[:len(prunes) // 2], prunes[len(prunes) // 2:]
    assert all(a["dead"] == 1 and a["blocks_checked"] >= 1 for a in dead)
    assert sum(a["blocks_checked"] for a in dead) == BLOCKS
    assert all(a["dead"] == 0 and a["blocks_checked"] >= 1 for a in live)


def test_corrupt_root_cannot_prune_a_live_split(served_store, oracle_rows):
    """A scrambled root directory (a larger block minimum) used to make the
    pruning read prune a live split, and the block went unread.  The host
    table still holds the written range, so the split is read, the root
    check quarantines the block, and the answers come from the other
    replicas, equal to the oracle."""
    cols, bad = oracle_rows
    b = 1
    FaultInjector(served_store, seed=11).corrupt_root(0, b)
    server = js.HailServer(served_store, js.ServerConfig(result_cache=False))
    lo = int(served_store.replicas[0].key_range[b, 0])
    queries = [q.HailQuery(filter=("visitDate", lo, lo + w),
                           projection=("sourceIP",)) for w in (0, 30, 900)]
    tickets = [server.submit(x) for x in queries]
    fl = server.flush()
    assert served_store.is_quarantined(0, b)
    assert fl.blocks_quarantined == 1
    for x, t in zip(queries, tickets):
        _, lo_, hi_ = x.filter
        sel = (cols["visitDate"] >= lo_) & (cols["visitDate"] <= hi_) & ~bad
        order = np.argsort(t.result.rows[ROWID])
        assert t.status == "done"
        np.testing.assert_array_equal(t.result.rows[ROWID][order],
                                      np.flatnonzero(sel))
        np.testing.assert_array_equal(t.result.rows["sourceIP"][order],
                                      cols["sourceIP"][sel])
