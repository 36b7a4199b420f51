"""HailServer: shared-scan batching, admission control, the governor-
integrated hot-block cache, and cache-invalidation races.

The acceptance scenario (ISSUE 4): 8 concurrent mixed-tenant queries over a
shared replica must issue ONE fused dispatch per (split, batch) — verified
via ``reader_stats`` — and return row-sets identical to 8 serial ``run_job``
calls, including under mid-batch demotion and node failover.  The property
test drives randomized interleavings of flushes, adaptive commits, direct
demotions and node failures against an uncached eager-store oracle.
"""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import governor as gv
from repro.core import mapreduce as mr
from repro.core import query as q
from repro.core import schema as sc
from repro.core import upload as up
from repro.core.parse import format_rows
from repro.core.schema import ROWID
from repro.kernels import ops
from repro.runtime import jobserver as js
from repro.runtime.cluster import SimulatedCluster
from repro.runtime.scheduler import run_schedule

from conftest import BLOCKS, PART

RANGES = [(7305, 7670), (0, 100), (5000, 20000), (7, 7),
          (123, 9999), (0, 1 << 30), (42, 4242), (1000, 8001)]
QUERIES = [q.HailQuery(filter=("visitDate", lo, hi),
                       projection=("sourceIP",)) for lo, hi in RANGES]


@pytest.fixture()
def served_store(uservisits_raw):
    """FRESH indexed store per test — the server attaches a cache to it."""
    _, raw = uservisits_raw
    store, _ = up.hail_upload(sc.USERVISITS, raw,
                              ["visitDate", "sourceIP", "adRevenue"],
                              partition_size=PART, n_nodes=6)
    return store


@pytest.fixture()
def lazy_store(uservisits_raw):
    _, raw = uservisits_raw
    store, _ = up.hail_upload(sc.USERVISITS, raw, index_columns=(),
                              partition_size=PART, n_nodes=6, replication=3)
    return store


def _oracle_rows(store, query):
    rows = q.collect(q.read_hail(store, query, q.plan(store, query)))
    order = np.argsort(rows[ROWID])
    return {k: v[order] for k, v in rows.items()}


def _assert_ticket_matches(ticket, want):
    assert ticket.status == "done"
    got = ticket.result.rows
    order = np.argsort(got[ROWID])
    assert ticket.result.n_rows == len(want[ROWID])
    for c in want:
        np.testing.assert_array_equal(got[c][order], want[c])


# ---------------------------------------------------------------------------
# Acceptance: one fused dispatch per (split, batch), row-sets == serial jobs
# ---------------------------------------------------------------------------


def test_shared_scan_batch_acceptance(served_store):
    # serial oracle FIRST: 8 independent run_job calls
    serial_rows = []
    with ops.stats_scope() as s_serial:
        for qq in QUERIES:
            st_ = mr.run_job(served_store, qq, reader="kernels")
            serial_rows.append(st_.results["n_rows"])
    serial_dispatches = s_serial.dispatches["hail_read"]

    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    tickets = [server.submit(qq, tenant=f"tenant{i % 3}")
               for i, qq in enumerate(QUERIES)]
    with ops.stats_scope() as s:
        fl = server.flush()
    # all 8 compatible queries formed ONE batch: one fused dispatch per
    # (split, batch), 8x fewer than the serial jobs issued
    assert fl.n_batches == 1 and fl.batch_sizes == [8]
    assert s.dispatches["hail_read"] == fl.n_splits
    assert s.dispatches["hail_read_batch"] == fl.n_splits
    assert serial_dispatches == 8 * fl.n_splits
    assert s.dispatches["pax_scan"] == 0 and s.dispatches["index_search"] == 0
    # row-sets identical to the serial jobs
    for ticket, qq, n_serial in zip(tickets, QUERIES, serial_rows):
        assert ticket.result.n_rows == n_serial
        _assert_ticket_matches(ticket, _oracle_rows(served_store, qq))
    assert fl.n_queries == 8 and fl.bytes_read > 0


def test_batch_width_compiles_once(served_store):
    """A fixed max_batch means ONE reader variant: later flushes with new
    ranges at the same width must not retrace."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=4))
    with ops.stats_scope() as s:
        for shift in (0, 1, 2):
            for lo, hi in RANGES[:4]:
                server.submit(q.HailQuery(
                    filter=("visitDate", lo + shift, hi + shift),
                    projection=("sourceIP",)))
            fl = server.flush()
            assert fl.batch_sizes == [4]
    assert s.traces["hail_read_batch"] <= 1
    assert s.dispatches["hail_read"] == 3 * fl.n_splits


def test_admission_control_per_tenant(served_store):
    cfg = js.ServerConfig(max_pending_per_tenant=2, max_pending_total=3)
    server = js.HailServer(served_store, cfg)
    server.submit(QUERIES[0], tenant="a")
    server.submit(QUERIES[1], tenant="a")
    with pytest.raises(js.AdmissionError):
        server.submit(QUERIES[2], tenant="a")       # tenant quota
    server.submit(QUERIES[2], tenant="b")
    with pytest.raises(js.AdmissionError):
        server.submit(QUERIES[3], tenant="c")       # global quota
    assert server.pending_count() == 3
    server.flush()
    assert server.pending_count() == 0
    server.submit(QUERIES[3], tenant="a")           # quota freed by flush
    fl = server.flush()
    assert fl.n_queries == 1


def test_incompatible_queries_split_batches(served_store):
    """Different filter columns (or projections) cannot share a scan — they
    form separate batches; an unfiltered query runs as a singleton."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    server.submit(QUERIES[0])
    server.submit(QUERIES[1])
    server.submit(q.HailQuery(filter=("sourceIP", 0, 1 << 30),
                              projection=("visitDate",)))
    server.submit(q.HailQuery(filter=None, projection=("sourceIP",)))
    fl = server.flush()
    assert fl.n_batches == 3 and sorted(fl.batch_sizes) == [1, 1, 2]
    for t in server.tickets:
        _assert_ticket_matches(t, _oracle_rows(served_store, t.query))


def test_flush_under_failover(served_store):
    """Mid-flush node death: lost splits re-plan to per-block retries (same
    path as run_job), every retry still goes through the fused batch reader,
    and row-sets stay exact."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    tickets = [server.submit(qq) for qq in QUERIES]
    with ops.stats_scope() as s:
        fl = server.flush(fail_node_at=0.5)
    assert fl.rescheduled_tasks > 0
    assert s.dispatches["hail_read"] == fl.n_splits   # retries fused too
    assert not served_store.namenode.dead             # revived after flush
    for ticket, qq in zip(tickets, QUERIES):
        _assert_ticket_matches(ticket, _oracle_rows(served_store, qq))


# ---------------------------------------------------------------------------
# Shared adaptive quantum + mid-batch demotion
# ---------------------------------------------------------------------------


def test_shared_build_quantum_across_tenants(lazy_store):
    """Concurrent tenants share ONE offer quantum per flush: 4 queries in a
    batch advance convergence by one job's worth, not 4 jobs' worth."""
    cfg = mr.AdaptiveConfig(offer_rate=0.5)
    quantum = mr.adaptive_quantum(lazy_store, cfg)
    server = js.HailServer(lazy_store, js.ServerConfig(max_batch=4,
                                                       adaptive=cfg))
    for i in range(4):
        server.submit(QUERIES[i], tenant=f"t{i}")
    fl = server.flush()
    assert fl.blocks_indexed == quantum               # one quantum, shared
    assert lazy_store.indexed_fraction("visitDate") == quantum / BLOCKS
    # convergence model unchanged: ceil(1/offer_rate) flushes to 1.0.
    # Ranges are PERTURBED per flush: an exact repeat would be served from
    # the result cache (zero scans — correct, but no piggyback builds;
    # convergence advances on ranges that actually scan)
    def shifted(i, r):
        col, lo, hi = QUERIES[i].filter
        return q.HailQuery(filter=(col, lo + r, hi + r),
                           projection=QUERIES[i].projection)
    for r in range(1, math.ceil(1 / cfg.offer_rate)):
        for i in range(4):
            server.submit(shifted(i, r), tenant=f"t{i}")
        server.flush()
    assert lazy_store.indexed_fraction("visitDate") == 1.0
    # converged: the next flush is pure index scan, zero build
    for i in range(4):
        server.submit(shifted(i, 100), tenant=f"t{i}")
    with ops.stats_scope() as s:
        fl = server.flush()
    assert fl.blocks_indexed == 0
    assert s.dispatches["full_scan_blocks"] == 0
    for t in server.tickets:
        _assert_ticket_matches(t, _oracle_rows(lazy_store, t.query))


def test_mid_batch_demotion_keeps_rowsets_exact(lazy_store, served_store):
    """Budget pressure DURING a flush: the shifted batch's builds evict the
    old column's replica mid-batch, invalidating its cache entries — and
    every ticket of the flush still matches the eager oracle."""
    gv.govern(lazy_store, max_indexed_blocks=BLOCKS)
    cfg = mr.AdaptiveConfig(offer_rate=1.0)
    # result_cache off: this test warms and checks the BLOCK cache — the
    # result tier would serve the repeat flush before it touches tier 1
    server = js.HailServer(lazy_store, js.ServerConfig(max_batch=4,
                                                       adaptive=cfg,
                                                       result_cache=False))
    for i in range(4):
        server.submit(QUERIES[i], tenant=f"t{i}")
    server.flush()                                    # converge visitDate
    assert lazy_store.indexed_fraction("visitDate") == 1.0

    # warm the cache on the victim replica (pure index scans, converged —
    # no adaptive work left on visitDate) so the demotion must invalidate
    for i in range(4):
        server.submit(QUERIES[i], tenant=f"t{i}")
    warm = server.flush()
    assert warm.blocks_indexed == 0 and warm.blocks_demoted == 0
    assert len(server.cache) > 0
    inval0 = server.cache.stats.invalidations

    shift = [q.HailQuery(filter=("sourceIP", lo, hi),
                         projection=("visitDate",))
             for lo, hi in [(0, 1 << 30), (1 << 10, 1 << 20),
                            (0, 1 << 16), (5, 5)]]
    for i, qq in enumerate(shift):
        server.submit(qq, tenant=f"t{i}")
    fl = server.flush()
    assert fl.blocks_demoted == BLOCKS                # mid-batch eviction
    assert fl.blocks_indexed > 0                      # re-keyed for the shift
    assert lazy_store.total_indexed_blocks() <= BLOCKS
    assert server.cache.stats.invalidations > inval0  # cache stayed coherent
    for t in server.tickets:
        _assert_ticket_matches(t, _oracle_rows(served_store, t.query))
    # old workload still answers exactly (full scan over demoted replica)
    server.submit(QUERIES[0])
    server.flush()
    _assert_ticket_matches(server.tickets[-1],
                           _oracle_rows(served_store, QUERIES[0]))


def test_row_ascii_store_served_via_hadoop_reader(uservisits_raw):
    """A row-layout (Hadoop baseline) store is servable too: queries run as
    singleton batches through read_hadoop, results equal to run_job."""
    _, raw = uservisits_raw
    store, _ = up.hdfs_upload(sc.USERVISITS, raw, replication=3, n_nodes=6)
    server = js.HailServer(store, js.ServerConfig(max_batch=8))
    t_filtered = server.submit(QUERIES[0])
    t_all = server.submit(q.HailQuery(filter=None, projection=("sourceIP",)))
    fl = server.flush()
    assert fl.n_batches == 2 and fl.batch_sizes == [1, 1]
    for t in (t_filtered, t_all):
        base = mr.run_job(store, t.query)
        assert t.result.n_rows == base.results["n_rows"] > 0
        got = q.collect(q.read_hadoop(store, t.query))
        order, gorder = (np.argsort(got[ROWID]),
                         np.argsort(t.result.rows[ROWID]))
        for c in t.query.projection + (ROWID,):
            np.testing.assert_array_equal(got[c][order],
                                          t.result.rows[c][gorder])


def test_one_flush_cannot_satisfy_its_own_hysteresis(lazy_store):
    """The governor's job boundary is the FLUSH, not the batch: a column
    seen for the first time — however many batches its flush takes — must
    not demote a warm index; the SECOND flush may."""
    gv.govern(lazy_store, max_indexed_blocks=10 * BLOCKS)
    cfg = mr.AdaptiveConfig(offer_rate=1.0)
    for col in ("visitDate", "sourceIP", "adRevenue"):
        mr.run_job(lazy_store, q.HailQuery(filter=(col, 0, 1 << 30),
                                           projection=("duration",)),
                   adaptive=cfg)
    assert all(r.sort_key is not None for r in lazy_store.replicas)
    server = js.HailServer(lazy_store, js.ServerConfig(max_batch=2,
                                                       adaptive=cfg))
    # 3 incompatible duration queries -> 2+ batches in ONE first-ever flush
    server.submit(q.HailQuery(filter=("duration", 0, 4000),
                              projection=("sourceIP",)))
    server.submit(q.HailQuery(filter=("duration", 0, 4000),
                              projection=("visitDate",)))
    server.submit(q.HailQuery(filter=("duration", 7, 7),
                              projection=("sourceIP",)))
    fl = server.flush()
    assert fl.n_batches >= 2
    assert fl.blocks_demoted == 0                 # one-off workload: no harm
    assert all(lazy_store.indexed_fraction(c) == 1.0
               for c in ("visitDate", "sourceIP", "adRevenue"))
    # the workload returns: the second distinct flush (a NEW job boundary,
    # so the first flush's misses now count as prior) crosses the threshold.
    # The range is perturbed — an exact repeat would be answered from the
    # result cache, which (correctly) never claims or demotes anything
    server.submit(q.HailQuery(filter=("duration", 0, 4001),
                              projection=("sourceIP",)))
    fl = server.flush()
    assert fl.blocks_demoted == BLOCKS
    assert lazy_store.indexed_fraction("duration") == 1.0


# ---------------------------------------------------------------------------
# Governor-integrated cache
# ---------------------------------------------------------------------------


def test_cache_traffic_feeds_access_log(served_store):
    """Cached reads are still governed traffic: the second (all-hit) flush
    advances the AccessLog exactly like the first (all-miss) one."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    rid = served_store.replica_for("visitDate")

    def log_hits():
        rec = served_store.access_log.get(rid, "visitDate") \
            if served_store.access_log else None
        return (rec.hits, rec.last_used) if rec else (0, 0)

    for qq in QUERIES:
        server.submit(qq)
    server.flush()
    hits1, used1 = log_hits()
    for qq in QUERIES:
        server.submit(qq)
    fl2 = server.flush()
    hits2, used2 = log_hits()
    assert fl2.cache_misses == 0 and fl2.cache_hits == fl2.n_splits
    assert hits2 - hits1 == hits1 > 0        # same attribution, cached
    assert used2 > used1                     # recency advanced: not LRU-cold
    # the second flush was the result tier's free lunch, and its replayed
    # attribution is what kept the AccessLog deltas above exact
    assert fl2.result_cache_hits == len(QUERIES) and fl2.n_splits == 0


# ---------------------------------------------------------------------------
# Result cache: the free-lunch tier
# ---------------------------------------------------------------------------


def test_result_cache_free_lunch_exact_and_subsumed(served_store):
    """A repeated range — and a narrower range subsumed by a cached one
    when the filter column is projected — must be answered with ZERO fused
    reader dispatches and rows identical to the uncached oracle."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    wide = q.HailQuery(filter=("visitDate", 0, 1 << 30),
                       projection=("visitDate", "sourceIP"))
    t_wide = server.submit(wide)
    server.flush()
    assert not t_wide.result.from_cache

    t_rep = server.submit(wide)              # exact repeat
    with ops.stats_scope() as s:
        fl = server.flush()
    assert t_rep.result.from_cache and fl.n_splits == 0
    assert s.dispatches["hail_read"] == 0
    assert s.dispatches["hail_read_batch"] == 0
    assert fl.result_cache_hits == 1 and fl.result_cache_misses == 0
    _assert_ticket_matches(t_rep, _oracle_rows(served_store, wide))

    narrow = q.HailQuery(filter=("visitDate", 7305, 7670),
                         projection=("visitDate", "sourceIP"))
    t_nar = server.submit(narrow)            # subsumed by the cached range
    with ops.stats_scope() as s:
        server.flush()
    assert t_nar.result.from_cache
    assert s.dispatches["hail_read"] == 0
    assert server.result_cache.stats.subsumed_hits == 1
    _assert_ticket_matches(t_nar, _oracle_rows(served_store, narrow))

    # filter column NOT projected: the cached rows can't be re-filtered,
    # so subsumption must NOT fire — the query scans and stays exact
    nar2 = q.HailQuery(filter=("visitDate", 7305, 7670),
                       projection=("sourceIP",))
    t3 = server.submit(nar2)
    server.flush()
    assert not t3.result.from_cache
    _assert_ticket_matches(t3, _oracle_rows(served_store, nar2))


def test_result_cache_counters_innermost_stats_scope(served_store):
    """reader_stats under NESTED stats_scope(): a result-cache
    short-circuit hit lands in the INNERMOST scope (the counters are
    looked up at call time), and merges outward on exit — same contract
    as every other reader counter."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    server.submit(QUERIES[0])
    server.flush()                           # fill
    server.submit(QUERIES[0])
    with ops.stats_scope() as outer:
        with ops.stats_scope() as inner:
            server.flush()                   # hit inside the inner scope
            inner_hits_live = ops.DISPATCH_COUNTS["result_cache_hits"]
        outer_hits_before_exit = dict(outer.dispatches).get(
            "result_cache_hits", 0)
    assert inner.dispatches["result_cache_hits"] == 1 == inner_hits_live
    assert inner.dispatches["result_cache_misses"] == 0
    assert outer_hits_before_exit == 1       # merged up when inner exited
    assert outer.dispatches["result_cache_hits"] == 1
    # block-cache counters obey the same innermost-scope rule: QUERIES[4]
    # misses the result tier (new range, filter col not projected so no
    # subsumption) but HITS the block cache (same col+proj gather key as
    # the QUERIES[0] fill).  A LIVE range is required here — a dead one
    # like QUERIES[1] now prunes every split and issues zero gathers.
    server.submit(QUERIES[4])
    with ops.stats_scope() as outer2:
        with ops.stats_scope() as inner2:
            server.flush()
    assert inner2.dispatches["result_cache_misses"] == 1
    assert inner2.dispatches["cache_hits"] > 0
    assert (outer2.dispatches["cache_hits"]
            == inner2.dispatches["cache_hits"])


def test_cache_capacity_scan_resistant_admission(served_store):
    """A capacity below the working set forces the admission filter to
    REJECT one-touch candidates instead of thrashing the residents (the
    pure-LRU predecessor evicted every resident and hit 0.0 here); the
    resident half keeps hitting, so the rate is strictly between 0 and 1.
    result_cache off: repeat flushes must exercise tier 1."""
    big = js.HailServer(served_store, js.ServerConfig(max_batch=1,
                                                      result_cache=False))
    for qq in QUERIES[:4]:
        big.submit(qq)
    big.flush()
    full_bytes = big.cache.stats.bytes_cached
    assert full_bytes > 0

    # an explicit cache_bytes budget replaces the attached unbounded cache
    # (a silently inherited unbounded cache would make the budget a no-op)
    server = js.HailServer(served_store, js.ServerConfig(
        max_batch=1, cache_bytes=full_bytes // 2, result_cache=False))
    small_cache = server.cache
    assert small_cache is served_store.block_cache is not big.cache
    assert small_cache.capacity_bytes == full_bytes // 2
    for _ in range(2):
        for qq in QUERIES[:4]:
            server.submit(qq)
        server.flush()
    assert small_cache.stats.admission_rejects > 0
    assert small_cache.stats.bytes_cached <= full_bytes // 2
    assert 0.0 < small_cache.stats.hit_rate < 1.0
    assert small_cache.recount() == small_cache.stats.bytes_cached
    # same budget again: the existing cache is REUSED, not reset
    again = js.HailServer(served_store, js.ServerConfig(
        cache_bytes=full_bytes // 2))
    assert again.cache is small_cache


def test_commit_and_demote_invalidate_cache(lazy_store):
    """The store's destructive transitions drop the touched replica's cache
    entries (a cached read can never observe a half-committed replica)."""
    server = js.HailServer(lazy_store, js.ServerConfig(max_batch=2))
    server.submit(QUERIES[0])
    server.submit(QUERIES[1])
    server.flush()
    assert len(server.cache) > 0
    mr._build_block_indexes(lazy_store, 0, list(range(BLOCKS)), "visitDate",
                            partition_size=PART)
    assert server.cache.stats.invalidations > 0
    inval = server.cache.stats.invalidations
    server.submit(QUERIES[0])
    server.flush()                            # re-fills from the new state
    _assert_ticket_matches(server.tickets[-1],
                           _oracle_rows(lazy_store, QUERIES[0]))
    lazy_store.demote_replica(0)
    assert server.cache.stats.invalidations > inval
    server.submit(QUERIES[0])
    server.flush()
    _assert_ticket_matches(server.tickets[-1],
                           _oracle_rows(lazy_store, QUERIES[0]))


# ---------------------------------------------------------------------------
# Scheduler bridge: shared-scan throughput
# ---------------------------------------------------------------------------


def test_flush_tasks_throughput_bridge(served_store):
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    for qq in QUERIES:
        server.submit(qq)
    fl = server.flush()
    tasks = js.flush_tasks(fl)
    assert len(tasks) == fl.n_splits
    assert all(t.n_queries == 8 for t in tasks)
    res = run_schedule(tasks, SimulatedCluster(n_nodes=4, map_slots=2),
                       spec_factor=None)
    # (query, split) answers, not distinct queries: Q * S
    assert res.n_query_answers == 8 * fl.n_splits
    assert res.makespan_s > 0


# ---------------------------------------------------------------------------
# Pallas interpret-mode runtime flag (satellite)
# ---------------------------------------------------------------------------


def test_interpret_default_follows_backend(monkeypatch):
    """No environment knob: interpret mode is on exactly where JAX's default
    backend is the CPU (these tests), off on an accelerator — where asking
    for it is refused rather than silently hiding the device."""
    import jax
    from repro.kernels import interpret_default
    assert jax.default_backend() == "cpu"
    assert interpret_default() is True
    assert ops.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_default() is False
    assert ops.interpret_mode() is False
    with pytest.raises(ValueError):
        ops.set_interpret(True)
    assert ops.interpret_mode() is False


def test_set_interpret_flips_and_clears_caches(served_store):
    assert ops.interpret_mode() is True
    try:
        ops.set_interpret(False)             # compiled Mosaic, at runtime
        assert ops.interpret_mode() is False
    finally:
        ops.set_interpret(None)              # back to following the backend
    assert ops.interpret_mode() is True
    # reader still correct after the cache-clearing round trip
    qp = q.plan(served_store, QUERIES[0])
    a = q.read_hail(served_store, QUERIES[0], qp)
    b = q.read_hail_kernels(served_store, QUERIES[0], qp)
    np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(b.mask))


# ---------------------------------------------------------------------------
# Property test: cache-invalidation races (commits, demotions, failures)
# ---------------------------------------------------------------------------

P_ROWS, P_PART = 256, 64
VMAX = 1 << 20


def _make_store_pair(seed, blocks=3):
    schema = sc.Schema("srv", tuple(sc.Column(f"c{i}") for i in range(3)))
    r = np.random.default_rng(seed)
    cols = {c.name: r.integers(0, VMAX, P_ROWS * blocks, dtype=np.int32)
            for c in schema.columns}
    raw = format_rows(schema, cols, bad_fraction=0.01,
                      seed=seed + 1).reshape(blocks, P_ROWS, -1)
    eager, _ = up.hail_upload(schema, raw, ["c0", "c1"],
                              partition_size=P_PART, n_nodes=4)
    lazy, _ = up.hail_upload(schema, raw, index_columns=(), replication=2,
                             partition_size=P_PART, n_nodes=4)
    return schema, eager, lazy


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 2**31 - 1),                 # data + schedule seed
       st.sampled_from([0.5, 1.0]),               # offer rate
       st.integers(2, 4))                         # queries per flush
def test_server_matches_uncached_oracle_under_races(seed, offer_rate, n_q):
    """Randomized interleavings of server flushes, adaptive index commits,
    direct demotions, node failures, quarantines and repairs: every ticket
    of every flush must equal the UNCACHED single-query oracle (fresh read
    over an eager, never-mutated store) — neither tier may serve stale
    replica state.  Ranges REPEAT (~half are drawn from history), so
    result-cache hits are exercised across every destructive transition."""
    schema, eager, lazy = _make_store_pair(seed)
    gv.govern(lazy, max_indexed_blocks=lazy.n_blocks)
    cfg = mr.AdaptiveConfig(offer_rate=offer_rate)
    server = js.HailServer(lazy, js.ServerConfig(max_batch=4, adaptive=cfg))
    rng = np.random.default_rng(seed ^ 0x5eed)
    verified = 0
    history: list[tuple] = []                  # (col, lo, hi) seen so far
    for step in range(6):
        col = ("c0", "c1")[int(rng.integers(0, 2))]
        qs = []
        for _ in range(n_q):
            if history and rng.random() < 0.5:   # repeat: result-cache path
                col_h, lo, hi = history[int(rng.integers(0, len(history)))]
                flt = (col_h, lo, hi)
            else:
                lo, hi = sorted(rng.integers(0, VMAX, 2).tolist())
                flt = (col, int(lo), int(hi))
            history.append(flt)
            qs.append(q.HailQuery(filter=flt, projection=("c2",)))
            server.submit(qs[-1], tenant=f"t{int(rng.integers(0, 3))}")
        action = int(rng.integers(0, 6))
        if action == 0:                        # race: node death mid-flush
            server.flush(fail_node_at=float(rng.uniform(0.1, 0.9)))
        elif action == 1:                      # race: serial adaptive job
            mr.run_job(lazy, qs[0], adaptive=cfg)   # commits mid-workload
            server.flush()
        elif action == 2:                      # race: direct demotion
            keyed = [i for i, r in enumerate(lazy.replicas)
                     if r.sort_key is not None and r.indexed.any()]
            if keyed:
                lazy.demote_replica(keyed[0])
            server.flush()
        elif action == 3:                      # race: quarantine a block
            b = int(rng.integers(0, lazy.n_blocks))
            alive = lazy.alive_replica_ids(b)
            if len(alive) >= 2:                # never strand the block
                lazy.quarantine_block(alive[0], b)
            server.flush()
            # heal before the next step: a LATER node-death step hitting
            # the sole surviving copy would (correctly) raise typed
            # UnrecoverableDataError and abort that flush — that
            # composition is test_fault's chaos subject, not this one's
            lazy.repair_blocks()
        elif action == 4:                      # race: repair what's hurt
            lazy.repair_blocks()
            server.flush()
        else:
            server.flush()
        for t in server.tickets[verified:]:    # results are immutable —
            _assert_ticket_matches(t, _oracle_rows(eager, t.query))
        verified = len(server.tickets)         # verify each exactly once
        assert lazy.total_indexed_blocks() <= lazy.n_blocks
    # repeats flowed through the result tier (hit or checked-and-missed) —
    # whether a given repeat HITS depends on the interleaving of
    # destructive transitions, which is exactly the point of the test
    assert (server.result_cache.stats.hits
            + server.result_cache.stats.misses) > 0


# ---------------------------------------------------------------------------
# Streaming completion, flush lifecycle fixes, and the async frontend (PR 8)
# ---------------------------------------------------------------------------

import jax.numpy as jnp

from repro.core.fault import FaultInjector
from repro.runtime.scheduler import Task
from repro.runtime.scrubber import Scrubber

# ranges DEAD against visitDate's [7000, 12000) domain vs provably live ones
DEAD_IDX = [i for i, (lo, hi) in enumerate(RANGES) if hi < 7000]
WIDE_IDX = RANGES.index((0, 1 << 30))


def test_streaming_per_query_completion(served_store):
    """A batch member live on no split (dead range) finalizes BEFORE any
    member that must wait on a scan barrier, each ticket's live-split set
    rides in ``queries_of_split``, and every row-set still matches the
    serial oracle."""
    server = js.HailServer(served_store,
                           js.ServerConfig(max_batch=8, result_cache=False))
    tickets = [server.submit(qq) for qq in QUERIES]
    fl = server.flush()
    for t in tickets:
        _assert_ticket_matches(t, _oracle_rows(served_store, t.query))
    # every ticket streamed a completion timestamp
    assert set(fl.query_done_s) == {t.ticket_id for t in tickets}
    # the live map is aligned with the executed splits and is exact at the
    # extremes: dead ranges ride no split, the full-domain range rides all
    assert len(fl.queries_of_split) == fl.n_splits == len(fl.split_s)
    dead_ids = {tickets[i].ticket_id for i in DEAD_IDX}
    wide_id = tickets[WIDE_IDX].ticket_id
    for live in fl.queries_of_split:
        assert wide_id in live
        assert not dead_ids & set(live)
    # dead-range members finalized before any scan-bound member
    dead_done = max(fl.query_done_s[i] for i in dead_ids)
    live_done = min(v for k, v in fl.query_done_s.items()
                    if k not in dead_ids)
    assert dead_done <= live_done
    # the scheduler bridge carries the same dependency sets
    tasks = js.flush_tasks(fl)
    sched = run_schedule(tasks, SimulatedCluster(n_nodes=4), None)
    assert set().union(*fl.queries_of_split) == set(
        sched.query_completion_s)
    assert all(i not in sched.query_completion_s for i in dead_ids)


def test_dead_range_batch_prunes_every_split(served_store):
    """A batch whose members all miss every block's key range dispatches
    ZERO fused reads — and the empty answers carry the STORED dtypes, not
    a hardcoded int32 (regression: the empty-assembly fallback)."""
    for rep in served_store.replicas:
        rep.cols["adRevenue"] = rep.cols["adRevenue"].astype(jnp.float32)
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    t1 = server.submit(q.HailQuery(filter=("visitDate", 7, 7),
                                   projection=("adRevenue",)))
    t2 = server.submit(q.HailQuery(filter=("visitDate", 0, 100),
                                   projection=("adRevenue",)))
    with ops.stats_scope() as s:
        fl = server.flush()
    assert s.dispatches["hail_read"] == 0 and fl.n_splits == 0
    for t in (t1, t2):
        assert t.status == "done" and t.result.n_rows == 0
        assert t.result.rows["adRevenue"].dtype == np.float32
        assert t.result.rows[ROWID].dtype == np.int32
        assert len(t.result.rows["adRevenue"]) == 0


def test_unrecoverable_batch_fails_typed_not_stranded(served_store):
    """Mid-flush ``UnrecoverableDataError``: the failed batch's tickets get
    a TYPED terminal status (never stranded "queued"), result-cache-served
    tickets of the same flush still complete, the injected-failure node is
    revived, and the boundary scrub still ticks (regression: flush() used
    to propagate and strand everything)."""
    scrub = Scrubber(served_store).attach()
    # no block cache: a warm hit would serve the pre-corruption decode and
    # mask the fault (hits legitimately skip re-verification)
    server = js.HailServer(served_store,
                           js.ServerConfig(max_batch=8, cache=False))
    warm = server.submit(QUERIES[0])
    server.flush()                               # clean fill of the result tier
    assert warm.status == "done"
    ticks0 = scrub.stats.ticks

    # silent corruption of EVERY replica of one block: any scan that plans
    # across it is unrecoverable by construction
    FaultInjector(served_store, seed=3).corrupt_replicas(
        2, served_store.replication, "visitDate")
    hit = server.submit(QUERIES[0])              # result tier: no scan needed
    doomed = server.submit(QUERIES[WIDE_IDX])
    fl = server.flush(fail_node_at=0.0)

    assert hit.status == "done" and hit.result.from_cache
    assert doomed.status == "failed" and doomed.result is None
    assert "block" in doomed.error
    assert fl.failed_queries == [doomed.ticket_id]
    assert not any(t.status == "queued" for t in server.tickets)
    assert not served_store.namenode.dead        # revived in the finally
    assert scrub.stats.ticks == ticks0 + 1       # boundary scrub still ran
    assert fl.scrub_s > 0.0


def test_result_cache_hit_is_mutation_proof(served_store):
    """A caller scribbling on a served answer RAISES instead of silently
    corrupting every future hit for that key (regression: hits aliased
    cache-owned arrays through a shallow dict copy)."""
    server = js.HailServer(served_store, js.ServerConfig(max_batch=8))
    server.submit(QUERIES[0])
    server.flush()                               # fill
    t_hit = server.submit(QUERIES[0])
    server.flush()
    assert t_hit.result.from_cache and t_hit.result.n_rows > 0
    with pytest.raises(ValueError):
        t_hit.result.rows["sourceIP"][:] = -1
    with pytest.raises(ValueError):
        t_hit.result.rows[ROWID][0] = 0
    # and the key keeps serving the exact answer
    t2 = server.submit(QUERIES[0])
    server.flush()
    assert t2.result.from_cache
    _assert_ticket_matches(t2, _oracle_rows(served_store, QUERIES[0]))


def test_flush_tasks_charges_demote_residue():
    """Demotion wall carried by no executed split must still reach the
    scheduler bridge: charged onto the first task, or onto a synthetic
    zero-duration task when the flush executed none."""
    fl = js.FlushStats(n_queries=1, n_batches=1, n_splits=0, batch_sizes=[1])
    fl.demote_residue_s = 0.25
    tasks = js.flush_tasks(fl)
    assert len(tasks) == 1
    assert tasks[0].duration_s == 0.0 and tasks[0].rekey_s == 0.25
    assert run_schedule(tasks, SimulatedCluster(n_nodes=2), None
                        ).makespan_s == pytest.approx(0.25)

    fl2 = js.FlushStats(n_queries=2, n_batches=1, n_splits=2,
                        batch_sizes=[2])
    fl2.split_s, fl2.build_s = [0.5, 0.5], [0.0, 0.0]
    fl2.demote_s, fl2.batch_of_split = [0.0, 0.1], [2, 2]
    fl2.queries_of_split = [(0, 1), (1,)]
    fl2.demote_residue_s = 0.25
    tasks2 = js.flush_tasks(fl2)
    assert len(tasks2) == fl2.n_splits           # no synthetic task
    assert tasks2[0].rekey_s == pytest.approx(0.25)
    assert tasks2[0].query_ids == (0, 1) and tasks2[1].query_ids == (1,)


def test_demote_wall_survives_pruned_and_terminal_batches(
        served_store, monkeypatch):
    """The demotion wall paid at claim time never vanishes, whether every
    split after the claim is dead-pruned or the batch dies terminally
    (regression: it was only charged when a dispatch succeeded)."""
    monkeypatch.setattr(js.mr, "claim_adaptive_replica",
                        lambda store, col, quantum: (None, 1, 0.5))
    cfg = js.ServerConfig(max_batch=8, result_cache=False,
                          adaptive=mr.AdaptiveConfig(offer_rate=1.0))
    # every split dead-pruned: the wall lands in the flush residue
    server = js.HailServer(served_store, cfg)
    server.submit(q.HailQuery(filter=("visitDate", 7, 7),
                              projection=("sourceIP",)))
    fl = server.flush()
    assert fl.n_splits == 0
    assert fl.demote_residue_s == pytest.approx(0.5)
    assert sum(t.rekey_s for t in js.flush_tasks(fl)) == pytest.approx(0.5)

    # batch dies terminally: the wall still reaches the bridge
    FaultInjector(served_store, seed=5).corrupt_replicas(
        1, served_store.replication, "visitDate")
    doomed = server.submit(QUERIES[WIDE_IDX])
    fl2 = server.flush()
    assert doomed.status == "failed"
    assert (sum(fl2.demote_s) + fl2.demote_residue_s
            == pytest.approx(0.5))
    assert (sum(t.rekey_s for t in js.flush_tasks(fl2))
            == pytest.approx(0.5))


# ---------------------------------------------------------------------------
# ServerFrontend: auto-flush, streaming latency, weighted-fair admission
# ---------------------------------------------------------------------------


def test_frontend_window_trigger_and_drain(served_store):
    """The oldest-pending window fires the flush (not the caller), later
    arrivals queue for the next cycle, and every answer matches the serial
    oracle with a per-query latency."""
    server = js.HailServer(served_store,
                           js.ServerConfig(result_cache=False))
    fe = js.ServerFrontend(server, js.FlushPolicy(window_s=1.0))
    for i, dt in [(0, 0.0), (2, 0.1), (4, 0.2)]:
        fe.offer(QUERIES[i], at=dt)
    assert fe.flushes == [] and fe.queue_depth == 3   # window not elapsed
    fe.offer(QUERIES[5], at=5.0)      # deadline 0.0+1.0 fires on the way
    assert len(fe.flushes) == 1 and fe.flushes[0].n_queries == 3
    assert fe.queue_depth == 1
    fe.drain()
    assert fe.queue_depth == 0 and len(fe.flushes) == 2
    assert len(fe.latencies) == 4 and not fe.failed
    for tk in fe.completed.values():
        _assert_ticket_matches(tk, _oracle_rows(served_store, tk.query))
    # the first arrival waited the full window before its flush even began
    first = server.tickets[0]
    assert fe.latencies[first.ticket_id] >= 1.0
    assert all(v >= 0.0 for v in fe.latencies.values())


def test_frontend_batch_full_trigger(served_store):
    """A compatible batch filling to max_batch fires immediately — no
    window wait — while the infinite-window baseline never self-fires."""
    server = js.HailServer(served_store,
                           js.ServerConfig(max_batch=2,
                                           result_cache=False))
    fe = js.ServerFrontend(server, js.FlushPolicy(window_s=100.0))
    fe.offer(QUERIES[0], at=0.0)
    assert fe.flushes == []
    fe.offer(QUERIES[2], at=0.0)      # same (col, projection): batch full
    assert len(fe.flushes) == 1 and fe.queue_depth == 0
    assert fe.flushes[0].n_queries == 2

    baseline = js.ServerFrontend(
        js.HailServer(served_store,
                      js.ServerConfig(max_batch=2, result_cache=False)),
        js.FlushPolicy(window_s=float("inf")))
    for i in range(4):
        baseline.offer(QUERIES[i], at=0.0)
    assert baseline.flushes == []     # inf window: drain-driven only
    baseline.drain()
    assert len(baseline.flushes) == 1
    assert baseline.flushes[0].n_queries == 4


def test_frontend_weighted_fair_admission(served_store):
    """Under overload (one batch per cycle), per-tenant WFQ weights decide
    the drain order: a weight-4 tenant gets ~4 of every 5 batch slots."""
    server = js.HailServer(served_store,
                           js.ServerConfig(max_batch=2, max_pending_total=64,
                                           result_cache=False))
    fe = js.ServerFrontend(server, js.FlushPolicy(
        window_s=float("inf"), max_batches_per_flush=1,
        weights={"A": 4.0, "B": 1.0}))
    qa = q.HailQuery(filter=("visitDate", 7000, 9000),
                     projection=("sourceIP",))
    qb = q.HailQuery(filter=("visitDate", 7000, 9000),
                     projection=("adRevenue",))   # distinct group per tenant
    for _ in range(3):
        fe.offer(qa, tenant="A", at=0.0)
        fe.offer(qb, tenant="B", at=0.0)
        fe.offer(qa, tenant="A", at=0.0)
        fe.offer(qb, tenant="B", at=0.0)
    assert fe.flushes == []           # inf window: nothing self-fires
    fe.drain()
    assert len(fe.flushes) == 6       # 6 batches of 2, one per cycle
    # reconstruct the per-cycle tenant from the server's submission order
    order, pos = [], 0
    for fl in fe.flushes:
        order.append(server.tickets[pos].tenant)
        pos += fl.n_queries
    # A/B vtimes: A's 2-query batch costs 2/4=0.5, B's costs 2/1=2.0, so
    # A drains its 3 batches in cycles 1/3/4 and B trails with 2 at the end
    assert order == ["A", "B", "A", "A", "B", "B"]
    # every answer is still exact, and later cycles queued behind earlier
    for tk in fe.completed.values():
        _assert_ticket_matches(tk, _oracle_rows(served_store, tk.query))
    assert fe.percentile_latency(99) >= fe.percentile_latency(50)


# ---------------------------------------------------------------------------
# Flight-recorder satellites: latency bookkeeping cross-checks (ISSUE 9)
# ---------------------------------------------------------------------------


def test_percentile_latency_nearest_rank_small_n():
    """Pinned nearest-rank semantics: every percentile is an actually
    observed sample — never interpolated — so small-N guards are exact."""
    fe = js.ServerFrontend.__new__(js.ServerFrontend)
    fe.latencies = {0: 0.3, 1: 0.1, 2: 0.2, 3: 0.4}
    assert fe.percentile_latency(25) == 0.1    # ceil(.25*4) = 1st smallest
    assert fe.percentile_latency(50) == 0.2    # ceil(.50*4) = 2nd
    assert fe.percentile_latency(51) == 0.3    # ceil(.51*4) = 3rd
    assert fe.percentile_latency(99) == 0.4    # ceil(.99*4) = the max
    assert fe.percentile_latency(100) == 0.4
    fe.latencies = {7: 1.5}                    # N=1: everything is the one
    assert fe.percentile_latency(1) == fe.percentile_latency(99) == 1.5
    fe.latencies = {}
    with pytest.raises(ValueError):
        fe.percentile_latency(50)


def test_percentile_latency_doctest_runs():
    import doctest
    results = doctest.DocTestRunner().run(
        doctest.DocTestFinder().find(js.ServerFrontend.percentile_latency,
                                     globs={"ServerFrontend":
                                            js.ServerFrontend})[0])
    assert results.attempted >= 3 and results.failed == 0


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 2**31 - 1),              # data + workload seed
       st.integers(2, 4))                      # queries per flush
def test_query_done_vs_modeled_completion_consistency(seed, n_q):
    """``FlushStats.query_done_s`` (measured stream-back offsets, keyed by
    ticket id) vs ``ScheduleResult.query_completion_s`` (modeled, keyed by
    the query ids the scheduler tasks carry) on randomized flushes with
    repeats (result-cache hits), adaptive commits and a demotion:

    * every done ticket streams back exactly once, within the flush wall;
    * the modeled side covers exactly the carried ids — a subset of the
      done tickets (no phantom/stale ids), each completing in
      ``(0, makespan]``;
    * a done ticket carried by NO task was answered without a scan
      (result tier, or pruned everywhere) and so completes at offset 0.
    """
    schema, eager, lazy = _make_store_pair(seed)
    cfg = mr.AdaptiveConfig(offer_rate=0.5)
    server = js.HailServer(lazy, js.ServerConfig(max_batch=4, adaptive=cfg))
    cm = server.config.cluster
    rng = np.random.default_rng(seed ^ 0xd21f7)
    history: list[tuple] = []
    verified = 0
    for step in range(4):
        for _ in range(n_q):
            if history and rng.random() < 0.5:   # repeat: result-tier path
                flt = history[int(rng.integers(0, len(history)))]
            else:
                lo, hi = sorted(rng.integers(0, VMAX, 2).tolist())
                flt = (("c0", "c1")[step % 2], int(lo), int(hi))
            history.append(flt)
            server.submit(q.HailQuery(filter=flt, projection=("c2",)))
        if step == 2:                            # race a demotion in
            keyed = [i for i, r in enumerate(lazy.replicas)
                     if r.sort_key is not None and r.indexed.any()]
            if keyed:
                lazy.demote_replica(keyed[0])
        fl = server.flush()
        new = server.tickets[verified:]
        verified = len(server.tickets)

        done = {t.ticket_id for t in new if t.status == "done"}
        assert set(fl.query_done_s) == done
        assert all(0.0 <= v <= fl.wall_s + 1e-6
                   for v in fl.query_done_s.values())

        tasks = js.flush_tasks(fl)
        sched = run_schedule(tasks,
                             SimulatedCluster(n_nodes=cm.n_nodes,
                                              map_slots=cm.map_slots),
                             spec_factor=None)
        carried = {qid for task in tasks for qid in task.query_ids}
        assert set(sched.query_completion_s) == carried
        assert carried <= done
        for qid, c in sched.query_completion_s.items():
            assert 0.0 < c <= sched.makespan_s + 1e-9
        for t in new:
            if t.status == "done" and t.ticket_id not in carried:
                assert t.result.from_cache or t.result.n_rows == 0
                assert t.explain().completion_s == 0.0
