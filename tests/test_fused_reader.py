"""Fused split reader: one-dispatch-per-split, zero per-query recompiles,
kernel/oracle equivalence on MIXED-REPLICA and FAILOVER splits, and the
Hadoop++ upload phase accounting."""
import numpy as np
import pytest

from repro.core import mapreduce as mr
from repro.core import query as q
from repro.core import schema as sc
from repro.core import upload as up
from repro.core.parse import format_rows
from repro.kernels import ops

Q1 = q.HailQuery(filter=("visitDate", 7305, 7670), projection=("sourceIP",))


def _equiv(store, query, qp, ids=None):
    a = q.read_hail(store, query, qp, ids)
    b = q.read_hail_kernels(store, query, qp, ids)
    am, bm = np.asarray(a.mask), np.asarray(b.mask)
    np.testing.assert_array_equal(am, bm)
    for c in query.projection:
        np.testing.assert_array_equal(np.asarray(a.cols[c])[am],
                                      np.asarray(b.cols[c])[bm])
    np.testing.assert_allclose(np.asarray(a.rows_read_frac),
                               np.asarray(b.rows_read_frac))


def test_one_dispatch_per_split(hail_store):
    qp = q.plan(hail_store, Q1)
    with ops.stats_scope() as s:
        q.read_hail_kernels(hail_store, Q1, qp)                # all blocks
        assert s.dispatches["hail_read"] == 1
        q.read_hail_kernels(hail_store, Q1, qp, [0, 2])        # 2-block split
    assert s.dispatches["hail_read"] == 2
    # no stray per-block kernel launches
    assert s.dispatches["pax_scan"] == 0
    assert s.dispatches["index_search"] == 0


def test_zero_recompiles_across_query_ranges(hail_store):
    qp = q.plan(hail_store, Q1)
    ranges = [(7305, 7670), (0, 100), (1, 2), (5000, 20000), (7, 7),
              (123, 9999), (0, 2**30), (42, 4242), (1000, 1001), (8, 800)]
    with ops.stats_scope() as s:
        for lo, hi in ranges:
            query = q.HailQuery(filter=("visitDate", lo, hi),
                                projection=("sourceIP",))
            q.read_hail_kernels(hail_store, query, qp)
    assert s.dispatches["hail_read"] == len(ranges)
    # at most the first call traces (0 when another test already warmed the
    # same store shape): ZERO recompiles after the first, across all ranges
    assert s.traces["hail_read"] <= 1


def test_mixed_replica_split_equivalence(hail_store):
    """One split whose blocks read from DIFFERENT replicas (index + full
    scan mixed) must still be a single fused dispatch and match the oracle."""
    qp = q.plan(hail_store, Q1)
    other = hail_store.replica_by_key("sourceIP")
    qp.replica_for_block[1::2] = other          # half the blocks fail over
    qp.index_scan[1::2] = False                 # ...to a non-matching index
    assert len(np.unique(qp.replica_for_block)) == 2
    with ops.stats_scope() as s:
        _equiv(hail_store, Q1, qp)
    assert s.dispatches["hail_read"] == 1       # one fused dispatch


def test_failover_split_equivalence(hail_store, oracle_rows):
    """After a node failure the re-planned blocks full-scan another replica;
    the fused reader must agree with the jnp reader on the new plan."""
    cols, bad = oracle_rows
    nn = hail_store.namenode
    victim = int(hail_store.replicas[
        hail_store.replica_by_key("visitDate")].nodes[0])
    nn.kill_node(victim)
    try:
        qp = q.plan(hail_store, Q1)
        assert not qp.index_scan.all()
        _equiv(hail_store, Q1, qp)
        res = q.collect(q.read_hail_kernels(hail_store, Q1, qp))
        m = (cols["visitDate"] >= 7305) & (cols["visitDate"] <= 7670) & ~bad
        np.testing.assert_array_equal(np.sort(res["sourceIP"]),
                                      np.sort(cols["sourceIP"][m]))
    finally:
        nn.revive()


def test_run_job_kernel_reader_with_failover(hail_store):
    """run_job(reader='kernels') routes every split — including the
    per-block retry splits re-planned after a node failure — through the
    fused reader, and results match the jnp reader job."""
    base = mr.run_job(hail_store, Q1, splitting="hail")
    with ops.stats_scope() as s:
        failed = mr.run_job(hail_store, Q1, splitting="hail",
                            fail_node_at=0.5, reader="kernels")
    assert failed.results["n_rows"] == base.results["n_rows"]
    assert failed.rescheduled_tasks > 0
    # exactly one fused dispatch per executed split, none per block
    assert s.dispatches["hail_read"] == failed.n_tasks
    assert s.dispatches["pax_scan"] == 0


def test_failover_mid_convergence_still_offers_indexing(uservisits_raw):
    """Kill a node mid-convergence: the re-queued splits of the dead node
    fall back to full scan on a surviving replica AND are still offered for
    adaptive indexing, so convergence survives the failure."""
    _, raw = uservisits_raw
    store, _ = up.hail_upload(sc.USERVISITS, raw, index_columns=(),
                              partition_size=128, n_nodes=6)
    cfg = mr.AdaptiveConfig(offer_rate=0.5)
    base = mr.run_job(store, Q1, adaptive=cfg)       # partial convergence
    frac0 = store.indexed_fraction("visitDate")
    assert 0.0 < frac0 < 1.0
    with ops.stats_scope() as s:
        failed = mr.run_job(store, Q1, adaptive=cfg, fail_node_at=0.5,
                            reader="kernels")
    assert failed.results["n_rows"] == base.results["n_rows"]
    assert failed.rescheduled_tasks > 0
    # every executed split (retries included) = one fused dispatch
    assert s.dispatches["hail_read"] == failed.n_tasks
    # unconverged blocks full-scanned...
    assert s.dispatches["full_scan_blocks"] > 0
    # ...and the job still built indexes while handling the failure
    assert failed.blocks_indexed > 0
    assert store.indexed_fraction("visitDate") > frac0
    # the store keeps converging to zero full-scan work after the failure
    while store.indexed_fraction("visitDate") < 1.0:
        mr.run_job(store, Q1, adaptive=cfg)
    with ops.stats_scope() as s2:
        final = mr.run_job(store, Q1, adaptive=cfg, reader="kernels")
    assert s2.dispatches["full_scan_blocks"] == 0
    assert final.results["n_rows"] == base.results["n_rows"]


def test_failover_races_demotion_kernel_reader(uservisits_raw):
    """Chaos: node loss racing a governor demotion in ONE kernels-reader
    job.  The re-queued splits must full-scan the just-demoted replica
    through the fused reader (one dispatch per split, no stray launches),
    still be offered rebuilds, and the shifted workload must reconverge."""
    from repro.core import governor as gv

    _, raw = uservisits_raw
    store, _ = up.hail_upload(sc.USERVISITS, raw, index_columns=(),
                              partition_size=128, n_nodes=6)
    n_blocks = store.n_blocks
    gv.govern(store, max_indexed_blocks=n_blocks)
    cfg = mr.AdaptiveConfig(offer_rate=1.0)
    base = mr.run_job(store, Q1, adaptive=cfg)       # converge on visitDate
    assert store.indexed_fraction("visitDate") == 1.0
    q2 = q.HailQuery(filter=("sourceIP", 0, 1 << 30),
                     projection=("visitDate",))
    base2 = mr.run_job(store, q2)                    # oracle row count
    with ops.stats_scope() as s:
        failed = mr.run_job(store, q2, adaptive=cfg, fail_node_at=0.5,
                            reader="kernels")
    # the shift evicted visitDate's replica while the failure was handled
    assert failed.blocks_demoted == n_blocks
    assert failed.rescheduled_tasks > 0
    assert failed.results["n_rows"] == base2.results["n_rows"]
    # every executed split (including post-demotion retries that full-scan
    # the demoted replica) = exactly one fused dispatch
    assert s.dispatches["hail_read"] == failed.n_tasks
    assert s.dispatches["pax_scan"] == 0
    assert s.dispatches["full_scan_blocks"] > 0
    assert s.dispatches["full_scan_blocks[sourceIP]"] > 0
    # the job still built indexes for the new workload under the budget
    assert failed.blocks_indexed > 0
    assert store.total_indexed_blocks() <= n_blocks
    while store.indexed_fraction("sourceIP") < 1.0:
        mr.run_job(store, q2, adaptive=cfg)
    with ops.stats_scope() as s2:
        final = mr.run_job(store, q2, adaptive=cfg, reader="kernels")
    assert s2.dispatches["full_scan_blocks"] == 0
    assert final.results["n_rows"] == base2.results["n_rows"]
    # the old workload still answers exactly, now by full scan
    refetch = mr.run_job(store, Q1, reader="kernels")
    assert refetch.results["n_rows"] == base.results["n_rows"]


def test_batch_reader_equals_serial_reads(hail_store):
    """Shared-scan batch reader: ONE fused dispatch serves Q queries with
    per-query masks identical to Q serial single-query reads — including on
    a MIXED split (index-scan and failover full-scan blocks together)."""
    ranges = [(7305, 7670), (0, 100), (5000, 20000), (7, 7), (0, 2**30)]
    queries = [q.HailQuery(filter=("visitDate", lo, hi),
                           projection=("sourceIP",)) for lo, hi in ranges]
    qp = q.plan(hail_store, Q1)
    other = hail_store.replica_by_key("sourceIP")
    qp.replica_for_block[1::2] = other          # half the blocks fail over
    qp.index_scan[1::2] = False
    with ops.stats_scope() as s:
        batch, shared = q.read_hail_batch(hail_store, queries, qp)
    assert s.dispatches["hail_read"] == 1       # one (split, batch) dispatch
    assert s.dispatches["hail_read_batch"] == 1
    for qq, res in zip(queries, batch):
        single = q.read_hail_kernels(hail_store, qq, qp)
        am, bm = np.asarray(single.mask), np.asarray(res.mask)
        np.testing.assert_array_equal(am, bm)
        for c in qq.projection:
            np.testing.assert_array_equal(np.asarray(single.cols[c])[am],
                                          np.asarray(res.cols[c])[bm])
        np.testing.assert_allclose(np.asarray(single.rows_read_frac),
                                   np.asarray(res.rows_read_frac))
    # physical shared-scan bytes: at most the widest per-block range summed
    fracs = np.stack([np.asarray(r.rows_read_frac) for r in batch])
    assert float(shared) == pytest.approx(
        fracs.max(axis=0).sum() * 4 * hail_store.rows_per_block * 2)


@pytest.fixture(scope="module")
def synthetic_store():
    """Four 1,024-row blocks of the 19-attribute Synthetic table, replicas
    clustered on attr0..attr2."""
    cols = sc.gen_synthetic(4 * 1024, seed=11)
    raw = format_rows(sc.SYNTHETIC, cols, bad_fraction=0.002).reshape(
        4, 1024, -1)
    store, _ = up.hail_upload(sc.SYNTHETIC, raw, ["attr0", "attr1", "attr2"],
                              partition_size=128, n_nodes=6)
    return store


def _syn_batch(store, n_q, n_proj, mixed):
    """n_q queries on attr0 projecting n_proj attributes, and a plan whose
    odd blocks (when ``mixed``) full-scan the replica clustered on attr1."""
    rng = np.random.default_rng(100 * n_q + n_proj)
    proj = tuple(f"attr{i}" for i in range(19))[-n_proj:]
    queries = []
    for _ in range(n_q):
        lo = int(rng.integers(0, 2**20))
        hi = lo + int(rng.choice([0, 3_000, 100_000, 2**20]))
        queries.append(q.HailQuery(filter=("attr0", lo, hi),
                                   projection=proj))
    qp = q.plan(store, queries[0])
    if mixed:
        qp.replica_for_block[1::2] = store.replica_by_key("attr1")
        qp.index_scan[1::2] = False
    return queries, qp


@pytest.mark.parametrize("mixed", [False, True], ids=["index", "mixed"])
@pytest.mark.parametrize("n_proj", [1, 2, 19])
@pytest.mark.parametrize("n_q", [1, 3, 8])
def test_batch_reader_split_in_program_equals_eager(synthetic_store, n_q,
                                                    n_proj, mixed):
    """``read_hail_batch``'s per-query masks, fractions and bytes, its
    columns and its shared bytes — split inside the reader's program —
    equal the same split made eagerly from one ``ops.hail_read_batch``
    call, which itself equals the jnp oracle: values, shapes and dtypes."""
    import jax.numpy as jnp
    from repro.kernels import ref

    store = synthetic_store
    queries, qp = _syn_batch(store, n_q, n_proj, mixed)
    proj_cols = queries[0].projection + (q.ROWID,)
    ids = np.arange(store.n_blocks)
    lohi = np.asarray([qq.filter[1:] for qq in queries], np.int32)
    mins, keys, proj, bad, uidx = q._gather_split_inputs(
        store, qp, ids, "attr0", proj_cols)
    assert set(np.asarray(uidx).tolist()) == ({0, 1} if mixed else {1})
    mask, out, frac = ops.hail_read_batch(mins, keys, proj, bad, uidx, lohi,
                                          partition_size=128,
                                          interpret=ops.interpret_mode())
    want = ref.hail_read_batch(mins, keys, proj, bad, jnp.asarray(uidx),
                               jnp.asarray(lohi), partition_size=128)
    for got, exp in zip((mask, out, frac), want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    batch, shared = q.read_hail_batch(store, queries, qp)
    col_bytes = 4 * store.rows_per_block * len(proj_cols)
    assert len(batch) == n_q
    for qi, res in enumerate(batch):
        eager = {"mask": mask[:, qi], "rows_read_frac": frac[:, qi],
                 "bytes_read": frac[:, qi].sum() * col_bytes}
        for name, exp in eager.items():
            got = getattr(res, name)
            assert (got.shape, got.dtype) == (exp.shape, exp.dtype), name
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       rtol=np.finfo(np.float32).eps)
        np.testing.assert_array_equal(np.asarray(res.mask),
                                      np.asarray(mask[:, qi]))
        assert list(res.cols) == list(proj_cols)
        for j, c in enumerate(proj_cols):
            exp = out[:, j]
            assert (res.cols[c].shape, res.cols[c].dtype) == \
                (exp.shape, exp.dtype)
            np.testing.assert_array_equal(np.asarray(res.cols[c]),
                                          np.asarray(exp))
    exp = frac.max(axis=1).sum() * col_bytes
    assert (shared.shape, shared.dtype) == (exp.shape, exp.dtype)
    np.testing.assert_allclose(float(shared), float(exp),
                               rtol=np.finfo(np.float32).eps)


def test_batch_read_is_one_program(synthetic_store):
    """Reading a split is ONE device program: with a batch width no other
    test uses, ``read_hail_batch`` compiles the reader and nothing else —
    no program slices its outputs — and new ranges compile nothing."""
    from bench.stats import compile_counter

    def compiles():
        return compile_counter().snapshot()["compiles"]

    store = synthetic_store
    queries, qp = _syn_batch(store, 7, 5, mixed=True)
    # the gather's own programs (concatenate, take) compile here, once
    q.read_hail_batch(store, queries[:1], qp)
    before = compiles()
    with ops.stats_scope() as s:
        res, shared = q.read_hail_batch(store, queries, qp)
        float(shared)
        assert compiles() - before == 1
        assert s.dispatches["hail_read_batch"] == 1
        moved = [q.HailQuery(filter=("attr0", qq.filter[1] // 2,
                                     qq.filter[2] // 2),
                             projection=qq.projection) for qq in queries]
        res, shared = q.read_hail_batch(store, moved, qp)
        float(shared)
    assert compiles() - before == 1
    assert s.dispatches["hail_read_batch"] == 2
    assert len(res) == 7 and len(res[0].cols) == 6


def test_run_job_pipelines_splits(hail_store):
    st = mr.run_job(hail_store, Q1, splitting="hail")
    assert len(st.split_s) == st.n_tasks
    assert st.results["n_rows"] > 0


# ---------------------------------------------------------------------------
# Hadoop++ upload phase accounting
# ---------------------------------------------------------------------------


def test_hadooppp_phase_accounting(uservisits_raw):
    _, raw = uservisits_raw
    _, s1 = up.hdfs_upload(sc.USERVISITS, raw, replication=3, n_nodes=6)
    _, spp = up.hadooppp_upload(sc.USERVISITS, raw, "visitDate", n_nodes=6)
    # the trojan job re-reads exactly what phase 1 wrote — and that extra
    # read is charged once, as modeled I/O, not also as compute wall
    assert spp.extra_read_bytes == s1.written_bytes
    assert set(spp.phases) == {"hdfs", "trojan_rewrite"}
    assert spp.wall_s == pytest.approx(sum(spp.phases.values()))
    # modeled cluster time charges the extra read sequentially
    from benchmarks.common import upload_model_seconds
    base = upload_model_seconds(spp)
    no_extra = upload_model_seconds(
        up.UploadStats(wall_s=spp.wall_s, ascii_bytes=spp.ascii_bytes,
                       written_bytes=spp.written_bytes))
    assert base > no_extra
