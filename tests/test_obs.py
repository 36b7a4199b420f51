"""Flight recorder (ISSUE 9): the unified metrics registry, simulated-clock
span tracing with Chrome trace-event (Perfetto) export, and per-query
``Ticket.explain()``.

Acceptance scenario: on a traced flush, ``explain()`` must account for
>= 95% of modeled end-to-end latency (vs ``query_completion_s``) for a
cold query, a result-cache hit, and a mid-flush quarantine survivor; the
exported trace must validate against the Chrome trace-event contract; and
the reader-counter registry audit auto-discovers every ``reader_stats``
key and proves ``reset_stats`` zeroes it and nested ``stats_scope``
scopes merge it.
"""
import doctest
import json

import numpy as np
import pytest

from repro.core import mapreduce as mr
from repro.core import query as q
from repro.core import schema as sc
from repro.core import upload as up
from repro.core.fault import FaultInjector
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime import jobserver as js
from repro.runtime.cluster import SimulatedCluster
from repro.runtime.scheduler import run_schedule

from conftest import PART

CLUSTER = mr.ClusterModel(n_nodes=6, map_slots=2)
# (0, 1<<30) is live on EVERY split; (7305, 7670) prunes to a few blocks
EXPLAIN_RANGES = [(0, 1 << 30), (7305, 7670), (42, 4242), (1000, 8001)]
EXPLAIN_QUERIES = [q.HailQuery(filter=("visitDate", lo, hi),
                               projection=("sourceIP",))
                   for lo, hi in EXPLAIN_RANGES]


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends untraced (install/uninstall is global)."""
    obs_trace.uninstall()
    yield
    obs_trace.uninstall()


@pytest.fixture()
def obs_store(uservisits_raw):
    """Fresh eager store per test — flushes attach caches, tests corrupt."""
    _, raw = uservisits_raw
    store, _ = up.hail_upload(sc.USERVISITS, raw,
                              ["visitDate", "sourceIP", "adRevenue"],
                              partition_size=PART, n_nodes=6)
    return store


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_doctests():
    results = doctest.testmod(obs_metrics)
    assert results.attempted > 0 and results.failed == 0


def test_registry_instruments_and_delta():
    reg = obs_metrics.MetricsRegistry()
    reg.inc("reads", 2, tenant="a")
    reg.inc("reads", 3, tenant="a")
    reg.inc("reads", 1, tenant="b")
    reg.gauge("depth").set(7)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("wall_s", v)
    snap = reg.snapshot()
    assert snap["reads{tenant=a}"] == 5 and snap["reads{tenant=b}"] == 1
    assert snap["depth"] == 7
    assert snap["wall_s.count"] == 4 and snap["wall_s.sum"] == 10.0
    assert snap["wall_s.min"] == 1.0 and snap["wall_s.max"] == 4.0
    h = reg.histogram("wall_s")
    assert h.percentile(50) == 2.0 and h.mean == 2.5
    # delta: only what moved
    reg.inc("reads", 4, tenant="b")
    d = reg.delta(snap)
    assert d["reads{tenant=b}"] == 4 and d["reads{tenant=a}"] == 0
    # counters are monotone; kind clashes are typed bugs
    with pytest.raises(ValueError):
        reg.counter("reads", tenant="a").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("reads", tenant="a")


def test_register_store_collector(obs_store):
    reg = obs_metrics.MetricsRegistry()
    col = obs_metrics.register_store(obs_store, reg)
    snap = reg.snapshot()
    assert snap["store.version"] == obs_store.version
    assert (snap["store.total_indexed_blocks"]
            == obs_store.total_indexed_blocks())
    obs_store.demote_replica(2)
    snap2 = reg.snapshot()
    assert snap2["store.version"] == obs_store.version > snap["store.version"]
    assert (snap2["store.total_indexed_blocks"]
            < snap["store.total_indexed_blocks"])
    reg.unregister_collector(col)
    obs_store.demote_replica(1)
    assert reg.snapshot()["store.version"] == snap2["store.version"]


# ---------------------------------------------------------------------------
# satellite: reader-counter registry completeness audit
# ---------------------------------------------------------------------------


def _reader_series(key: str) -> str:
    name, labels = obs_metrics.parse_reader_key(key)
    return (f"reader.{name}{{column={labels['column']}}}" if labels
            else f"reader.{name}")


def _exercise(store):
    """Touch as many distinct reader counters as one workload can: fused
    single + batched reads (verify on fill), a quarantine, a repair."""
    mr.run_job(store, EXPLAIN_QUERIES[0], reader="kernels", cluster=CLUSTER)
    server = js.HailServer(store, js.ServerConfig(
        max_batch=2, cluster=CLUSTER, cache=False, result_cache=False))
    for qq in EXPLAIN_QUERIES[:2]:
        server.submit(qq)
    server.flush()
    store.quarantine_block(1, 0)
    store.repair_blocks()


def test_reader_counter_registry_audit(obs_store):
    """AUTO-DISCOVER every reader_stats key the workload produces; each one
    must (a) be mirrored by the registry's reader collector, (b) read 0
    after ``reset_stats`` — in the source AND in the registry (no stale
    gauges), (c) merge exactly across nested ``stats_scope`` blocks."""
    ops.reset_stats()
    _exercise(obs_store)
    discovered = {k: v for k, v in ops.reader_stats()["dispatches"].items()
                  if v}
    assert len(discovered) >= 8, f"workload too narrow: {discovered}"
    assert "hail_read" in discovered and "verify_blocks" in discovered
    assert "blocks_quarantined" in discovered
    assert any(k.startswith("index_scan_blocks[") for k in discovered)

    # (a) registry mirrors every discovered key, per-column labels parsed
    snap = obs_metrics.snapshot()
    for key, v in discovered.items():
        assert snap[_reader_series(key)] == v, key

    # (b) reset zeroes the source and the mirrored gauges
    ops.reset_stats()
    after = ops.reader_stats()["dispatches"]
    assert all(after.get(k, 0) == 0 for k in discovered)
    snap0 = obs_metrics.snapshot()
    for key in discovered:
        assert snap0[_reader_series(key)] == 0, key

    # (c) nested scopes merge: outer totals == pre-inner + inner, per key
    with ops.stats_scope(merge=False) as outer:
        _exercise(obs_store)
        solo = dict(ops.reader_stats()["dispatches"])
        with ops.stats_scope() as inner:
            _exercise(obs_store)
    for k in set(solo) | set(inner.dispatches):
        assert (outer.dispatches[k]
                == solo.get(k, 0) + inner.dispatches[k]), k
    # merge=False: the scopes' counts never reach the module globals
    assert all(v == 0 for v in ops.reader_stats()["dispatches"].values())


def test_observe_flush_and_job_series(obs_store):
    before = obs_metrics.snapshot()
    st = mr.run_job(obs_store, EXPLAIN_QUERIES[1], cluster=CLUSTER)
    server = js.HailServer(obs_store, js.ServerConfig(max_batch=4,
                                                      cluster=CLUSTER))
    for qq in EXPLAIN_QUERIES:
        server.submit(qq, tenant="alice")
    fl = server.flush()
    d = obs_metrics.delta(before)
    assert d["job.jobs"] == 1 and d["job.tasks"] == st.n_tasks
    assert d["job.bytes_read"] == st.bytes_read
    assert d["flush.flushes"] == 1 and d["flush.queries"] == fl.n_queries
    assert d["flush.splits"] == fl.n_splits
    assert d["flush.tenant_queries{tenant=alice}"] == len(EXPLAIN_QUERIES)
    assert d["flush.cache_misses{tier=result}"] == fl.result_cache_misses
    assert d["flush.query_done_s.count"] == len(fl.query_done_s)


# ---------------------------------------------------------------------------
# span tracing + Chrome trace-event contract
# ---------------------------------------------------------------------------


def test_traced_flush_exports_valid_chrome_trace(obs_store, tmp_path):
    tracer = obs_trace.install()
    server = js.HailServer(obs_store, js.ServerConfig(max_batch=4,
                                                      cluster=CLUSTER))
    fe = js.ServerFrontend(server, js.FlushPolicy(window_s=0.5))
    for k, qq in enumerate(EXPLAIN_QUERIES):
        fe.offer(qq, tenant=f"t{k % 2}", at=k * 0.3)
    fe.drain()
    obs_trace.uninstall()

    path = tmp_path / "trace.json"
    exported = tracer.export(str(path))
    assert obs_trace.validate_chrome_trace(exported) == []
    with open(path) as f:
        assert obs_trace.validate_chrome_trace(json.load(f)) == []

    evs = exported["traceEvents"]
    names = {e["name"] for e in evs}
    # flush lifecycle on the measured wall
    assert {"flush", "plan", "result_cache_probe", "batching", "dispatch",
            "wait", "verify_blocks", "finalize"} <= names
    # simulated timeline: scheduler node tracks + per-tenant query slices
    sim_tracks = {e["args"]["name"] for e in evs
                  if e["ph"] == "M" and e["name"] == "thread_name"
                  and e["pid"] == obs_trace.PID_SIM}
    assert any(t.startswith("node ") for t in sim_tracks)
    assert any(t.startswith("tenant ") for t in sim_tracks)
    # flow arrows connect query slices to the splits they waited on
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert {e["ph"] for e in flows} >= {"s", "f"}
    started = {e["id"] for e in flows if e["ph"] == "s"}
    finished = {e["id"] for e in flows if e["ph"] == "f"}
    assert finished and finished <= started


def _spans(events, name):
    """(start, end, args) of each closed B/E ``name`` span, by start."""
    open_, out = {}, []
    for ev in events:
        if ev.get("name") != name:
            continue
        if ev["ph"] == "B":
            open_.setdefault(ev["tid"], []).append(ev)
        elif ev["ph"] == "E":
            b = open_[ev["tid"]].pop()
            out.append((b["ts"], ev["ts"], b["args"]))
    return sorted(out, key=lambda sp: sp[0])


def _traced_flush(server, queries):
    """Submit ``queries``, flush once under a fresh tracer.  -> (tracer,
    tickets, FlushStats)."""
    tickets = [server.submit(qq) for qq in queries]
    tracer = obs_trace.install()
    try:
        stats = server.flush()
    finally:
        obs_trace.uninstall()
    return tracer, tickets, stats


def test_traced_flush_span_tree(obs_store):
    """Every step of a flush has its span, nested as the work is: prune,
    gather and issue inside a split's dispatch, one wait per dispatched
    split, one finalize per answered ticket, the ticket ids carried."""
    server = js.HailServer(obs_store, js.ServerConfig(
        max_batch=4, cluster=CLUSTER, result_cache=False))
    # the second batch asks for dates past every block's last key: each
    # of its splits is pruned (dead) and its tickets finalize at once
    past = [q.HailQuery(filter=("visitDate", lo, lo + 10),
                        projection=("sourceIP",)) for lo in (1 << 20, 1 << 21)]
    queries = EXPLAIN_QUERIES + past
    tracer, tickets, fs = _traced_flush(server, queries)
    assert obs_trace.validate_chrome_trace(tracer.export()) == []
    evs = tracer.events
    assert not [e for e in evs if e["ph"] in ("X", "i")
                and e["name"] in ("flush", "batch", "split", "finalize",
                                  "block_cache_hit", "hail_read_batch")]
    (flush,) = _spans(evs, "flush")
    batches = _spans(evs, "batch")
    assert len(batches) == fs.n_batches == 2
    dispatches = _spans(evs, "dispatch")
    for name in ("prune", "gather", "issue"):
        for s, e, _ in _spans(evs, name):
            assert any(ds <= s and e <= de for ds, de, _ in dispatches), name
    assert len(_spans(evs, "prune")) == len(dispatches)
    live = [a["live"] for _, _, a in dispatches]
    assert [] in live, "the flush should hold a dead split"
    assert sum(bool(lv) for lv in live) == fs.n_splits
    assert len(_spans(evs, "wait")) == fs.n_splits
    assert len(_spans(evs, "issue")) == fs.n_splits
    for s, e, a in dispatches + _spans(evs, "wait"):
        assert any(bs <= s and e <= be and set(a["live"]) <= set(ba["tickets"])
                   for bs, be, ba in batches)
    assert all(flush[0] <= s and e <= flush[1] for s, e, _ in batches)
    fin = _spans(evs, "finalize")
    answered = [t for t in tickets if t.status == "done"]
    assert len(fin) == len(answered) == len(queries)
    assert sorted(a["ticket"] for _, _, a in fin) == \
        sorted(t.ticket_id for t in answered)
    assert {tuple(b["tickets"]) for _, _, b in batches} == \
        {(0, 1, 2, 3), (4, 5)}
    assert sum(a["cache_hits"] + a["cache_misses"]
               for _, _, a in _spans(evs, "gather")) >= fs.n_splits


def test_finalize_counts_each_device_array_once(obs_store, monkeypatch):
    """``d2h_bytes``: the nbytes of the distinct device arrays the finalizes
    copied to the host (a batch's shared projection columns once);
    ``answer_bytes``: rows x (projected columns + row id) x 4."""
    import jax
    server = js.HailServer(obs_store, js.ServerConfig(
        max_batch=4, cluster=CLUSTER, result_cache=False))
    produced, copied = set(), {}
    read = q.read_hail_batch

    def recording_read(*a, **kw):
        res, shared = read(*a, **kw)
        for r in res:
            produced.update(id(x) for x in (r.mask, *r.cols.values()))
        return res, shared

    class HostCopies:                  # numpy as the server sees it
        def __getattr__(self, k):
            return getattr(np, k)

        def asarray(self, a, *args, **kw):
            if isinstance(a, jax.Array):
                copied[id(a)] = a       # held: ids stay distinct
            return np.asarray(a, *args, **kw)

    monkeypatch.setattr(q, "read_hail_batch", recording_read)
    monkeypatch.setattr(js, "np", HostCopies())
    tracer, tickets, fs = _traced_flush(server, EXPLAIN_QUERIES)
    fin = _spans(tracer.events, "finalize")
    want = sum(a.nbytes for k, a in copied.items() if k in produced)
    assert want > 0
    assert sum(a["d2h_bytes"] for _, _, a in fin) == want
    shared_cols = 4 * 2 * obs_store.rows_per_block * obs_store.n_blocks
    assert max(a["d2h_bytes"] for _, _, a in fin) >= shared_cols
    by_ticket = {a["ticket"]: a for _, _, a in fin}
    for t in tickets:
        a = by_ticket[t.ticket_id]
        assert a["rows"] == t.result.n_rows
        assert a["answer_bytes"] == t.result.n_rows * (1 + 1) * 4 == \
            sum(v.nbytes for v in t.result.rows.values())


def test_spans_reach_the_profiler_trace(obs_store, tmp_path):
    """While a tracer is installed each span is also a profiler
    annotation: the ``.xplane.pb`` holds as many as the obs events."""
    import collections
    import jax
    server = js.HailServer(obs_store, js.ServerConfig(
        max_batch=4, cluster=CLUSTER, result_cache=False))
    for qq in EXPLAIN_QUERIES:
        server.submit(qq)
    server.flush()                     # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tracer, _, _ = _traced_flush(server, EXPLAIN_QUERIES)
    finally:
        jax.profiler.stop_trace()
    (xp,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(xp))
    host = collections.Counter(
        e.name for p in data.planes if p.name.startswith("/host")
        for ln in p.lines for e in ln.events)
    for name in ("dispatch", "wait", "finalize"):
        n = len(_spans(tracer.events, name))
        assert n > 0 and host[name] == n, name


def test_trace_validator_rejects_malformed():
    def errs(events):
        return obs_trace.validate_chrome_trace({"traceEvents": events})

    ok = {"ph": "i", "pid": 1, "tid": 1, "name": "x", "ts": 1.0, "s": "t"}
    assert errs([ok]) == []
    assert errs([{**ok, "ph": "Z"}])                  # unknown phase
    assert errs([{**ok, "ts": -1.0}])                 # negative ts
    assert errs([{**ok, "ts": "soon"}])               # non-numeric ts
    assert errs([{"ph": "X", "pid": 1, "tid": 1, "name": "x",
                  "ts": 0, "dur": -5}])               # negative dur
    assert errs([{"ph": "E", "pid": 1, "tid": 1, "name": "x", "ts": 1}])
    assert errs([{"ph": "B", "pid": 1, "tid": 1, "name": "a", "ts": 1},
                 {"ph": "E", "pid": 1, "tid": 1, "name": "b", "ts": 2}])
    assert errs([{"ph": "B", "pid": 1, "tid": 1, "name": "a", "ts": 1}])
    assert errs([{"ph": "B", "pid": 1, "tid": 1, "name": "a", "ts": 5},
                 {"ph": "E", "pid": 1, "tid": 1, "name": "a", "ts": 1}])
    assert obs_trace.validate_chrome_trace("nope")
    assert obs_trace.validate_chrome_trace({"events": []})
    # B/E discipline is per-(pid, tid): interleaved tracks are fine
    assert errs([{"ph": "B", "pid": 1, "tid": 1, "name": "a", "ts": 1},
                 {"ph": "B", "pid": 1, "tid": 2, "name": "b", "ts": 2},
                 {"ph": "E", "pid": 1, "tid": 1, "name": "a", "ts": 3},
                 {"ph": "E", "pid": 1, "tid": 2, "name": "b", "ts": 4}]) == []


def test_tracing_disabled_is_noop(obs_store, monkeypatch):
    assert not obs_trace.enabled() and obs_trace.current() is None
    with obs_trace.span("x", track="t") as s:
        assert s is None                      # shared null context
    obs_trace.instant("x")
    obs_trace.complete_wall("x", 0.0, 1.0)
    obs_trace.complete_sim("x", 0.0, 1.0)
    obs_trace.flow("s", 1, 0.0, track="t")
    # a full (untraced) flush stays correct and records nothing: no tracer
    # method runs, so neither an obs event nor a profiler annotation
    idle = obs_trace.Tracer()
    n0 = len(idle.events)

    def boom(*a, **kw):
        raise AssertionError("a tracer recorded an untraced flush")

    for method in ("span", "instant", "complete_wall"):
        monkeypatch.setattr(obs_trace.Tracer, method, boom)
    server = js.HailServer(obs_store, js.ServerConfig(max_batch=4,
                                                      cluster=CLUSTER))
    for qq in EXPLAIN_QUERIES:
        server.submit(qq)
    server.flush()
    assert all(t.status == "done" for t in server.tickets)
    assert len(idle.events) == n0 and obs_trace.current() is None


# ---------------------------------------------------------------------------
# acceptance: Ticket.explain() accounts >= 95% of modeled latency
# ---------------------------------------------------------------------------


def _assert_accounts(rec):
    assert rec.accounted_fraction >= 0.95
    if rec.completion_s > 0:      # exact decomposition, not just >= 95%
        assert abs(rec.accounted_s - rec.completion_s) \
            <= 1e-9 + 1e-6 * rec.completion_s


def test_explain_cold_and_result_hit(obs_store):
    server = js.HailServer(obs_store, js.ServerConfig(max_batch=4,
                                                      cluster=CLUSTER))
    for qq in EXPLAIN_QUERIES:
        server.submit(qq, tenant="alice")
    fl = server.flush()
    n = len(EXPLAIN_QUERIES)
    for t in server.tickets[:n]:
        _assert_accounts(t.explain())

    rec = server.tickets[0].explain()        # (0, 1<<30): live on all splits
    assert rec.status == "done" and rec.outcome == "cold"
    assert rec.trigger == "manual"
    assert rec.completion_s > 0 and rec.splits
    assert rec.index_blocks + rec.full_blocks > 0
    assert rec.sched_wait_s + rec.read_s + rec.build_s + rec.rekey_s \
        == pytest.approx(rec.completion_s)
    # agrees with an independent bridge of the same FlushStats
    sched = run_schedule(js.flush_tasks(fl),
                         SimulatedCluster(CLUSTER.n_nodes, CLUSTER.map_slots),
                         spec_factor=None)
    assert rec.completion_s == pytest.approx(
        sched.query_completion_s[rec.ticket_id])
    assert rec.done_wall_s is not None and rec.done_wall_s >= 0
    assert "sched wait" in rec.render() and str(rec) == rec.render()

    # warm repeat: the result tier answers, explain says so
    for qq in EXPLAIN_QUERIES:
        server.submit(qq, tenant="alice")
    server.flush()
    hit = server.tickets[n].explain()
    assert hit.outcome == "result_hit"
    assert hit.completion_s == 0.0 and hit.accounted_fraction >= 0.95
    assert hit.flush["result_cache_hits"] == n


def test_explain_quarantine_survivor(obs_store):
    FaultInjector(obs_store, seed=1).corrupt_chunk(0, 2, "visitDate")
    server = js.HailServer(obs_store, js.ServerConfig(max_batch=2,
                                                      cluster=CLUSTER,
                                                      result_cache=False))
    server.submit(EXPLAIN_QUERIES[0])         # live on the corrupt block
    fl = server.flush()
    assert fl.blocks_quarantined == 1 and fl.corrupt_retries >= 1
    tk = server.tickets[0]
    assert tk.status == "done"
    rec = tk.explain()
    _assert_accounts(rec)
    assert rec.quarantined == 1 and rec.retries_survived >= 1
    assert rec.outcome != "failed" and rec.completion_s > 0
    assert "survived" in rec.render()


def test_explain_frontend_latency_decomposition(obs_store):
    server = js.HailServer(obs_store, js.ServerConfig(max_batch=2,
                                                      cluster=CLUSTER))
    fe = js.ServerFrontend(server, js.FlushPolicy(window_s=0.5))
    for k, qq in enumerate(EXPLAIN_QUERIES):
        fe.offer(qq, tenant=f"t{k % 2}", at=k * 0.25)
    fe.drain()
    assert len(fe.latencies) == len(EXPLAIN_QUERIES)
    for t in server.tickets:
        rec = t.explain()
        _assert_accounts(rec)
        assert rec.trigger in ("batch_full", "window", "drain")
        # frontend latency == queue wait + modeled service, exactly
        assert rec.latency_s == pytest.approx(fe.latencies[t.ticket_id])
        assert rec.latency_s == pytest.approx(rec.queue_wait_s
                                              + rec.completion_s)


def test_explain_before_flush_raises(obs_store):
    server = js.HailServer(obs_store, js.ServerConfig(cluster=CLUSTER))
    tk = server.submit(EXPLAIN_QUERIES[0])
    with pytest.raises(RuntimeError, match="not been flushed"):
        tk.explain()
