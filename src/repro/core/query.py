"""HAIL query pipeline (paper §4): annotations, replica planning, record
readers (index scan vs full scan), PAX->row reconstruction.

Replica selection mirrors §4.3: for each block, prefer an *alive* replica
whose clustered index matches the filter attribute; otherwise fall back to
any alive replica with a full scan (failover path — Fig 8's experiment).

Record readers are jit'd, *batched over many blocks per call* — that batching
is exactly what HailSplitting enables (ONE dispatch per split instead of one
per block); the benchmarks measure both policies.  Two properties keep the
hot path dispatch- and compile-free:

* (lo, hi) are TRACED arguments everywhere (SMEM runtime scalars for the
  Pallas readers, ordinary traced scalars for the jnp readers), so a
  compiled reader is reused across every query against the same store
  shape — zero per-query recompiles;
* ``read_hail_kernels`` issues exactly one fused ``hail_read`` pallas_call
  per split regardless of block count, including MIXED-replica and failover
  splits (per-block ``use_index`` flags select pruned index scan vs full
  scan inside the kernel);
* ``read_hail_batch`` extends that to a QUERY dimension: one pallas_call
  serves a whole batch of compatible concurrent queries (same filter
  column, same projection) with per-query match masks — the HailServer's
  shared-scan hot path — optionally through the store's hot-block cache
  (``core/cache.BlockCache``), whose traffic still feeds the governor's
  AccessLog.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import governor as gov
from repro.core import index as idx
from repro.core import parse as ps
from repro.core.fault import CorruptBlockError, UnrecoverableDataError
from repro.core.schema import ROWID, Schema
from repro.core.store import BlockStore
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class HailQuery:
    """filter: (column, lo, hi) inclusive range (point = lo==hi)."""
    filter: Optional[tuple[str, int, int]]
    projection: tuple[str, ...]

    @property
    def filter_col(self) -> Optional[str]:
        return self.filter[0] if self.filter else None


def hail_annotation(schema: Schema, filter: str = "", projection: str = ""):
    """Parse the paper's @HailQuery annotation syntax:

      @HailQuery(filter="@3 between(7305,7670)", projection={@1})
      filter forms: "@k between(a,b)" | "@k = v"   (@k is 1-based position)
    """
    flt = None
    if filter:
        m = re.match(r"@(\d+)\s+between\((-?\d+),\s*(-?\d+)\)", filter.strip())
        if m:
            col = schema.columns[int(m.group(1)) - 1].name
            flt = (col, int(m.group(2)), int(m.group(3)))
        else:
            m = re.match(r"@(\d+)\s*=\s*(-?\d+)", filter.strip())
            if not m:
                raise ValueError(f"bad filter annotation: {filter!r}")
            col = schema.columns[int(m.group(1)) - 1].name
            v = int(m.group(2))
            flt = (col, v, v)
    proj = tuple(schema.columns[int(p) - 1].name
                 for p in re.findall(r"@(\d+)", projection))
    return HailQuery(filter=flt, projection=proj or schema.names)


def hail_query(filter: str = "", projection: str = "", schema: Schema = None):
    """Decorator flavour: @hail_query(filter=..., projection=...) on a map fn."""
    def deco(fn):
        fn.__hail_query__ = hail_annotation(schema, filter, projection)
        return fn
    return deco


# ---------------------------------------------------------------------------
# Planning (the JobClient/JobTracker side)
# ---------------------------------------------------------------------------

FULL_SCAN = -1


@dataclasses.dataclass
class QueryPlan:
    replica_for_block: np.ndarray    # (n_blocks,) replica idx used for reading
    index_scan: np.ndarray           # (n_blocks,) bool: index scan possible
    nodes: np.ndarray                # (n_blocks,) datanode serving the read


def plan(store: BlockStore, query: HailQuery) -> QueryPlan:
    """Replica selection against the store's LIVE per-block index state.

    A replica qualifies a block for index scan only if its clustered index
    both matches the filter attribute AND has actually been built for that
    block (``Replica.block_indexed``) — under adaptive indexing blocks of
    the same replica flip from full scan to index scan as running jobs
    commit indexes, and re-planning picks that up job over job.
    """
    nb = store.n_blocks
    rep = np.zeros(nb, dtype=np.int64)
    is_idx = np.zeros(nb, dtype=bool)
    nodes = np.zeros(nb, dtype=np.int64)
    want = query.filter_col
    for b in range(nb):
        alive = store.alive_replica_ids(b)
        if not alive:
            raise UnrecoverableDataError(
                f"block {b}: all replicas lost or quarantined")
        choice = None
        if want is not None and store.layout == "pax":
            for i in alive:
                if (store.replicas[i].sort_key == want
                        and store.replicas[i].block_indexed(b)):
                    choice = i
                    is_idx[b] = True
                    break
        if choice is None:
            choice = alive[0]
        rep[b] = choice
        nodes[b] = int(store.replicas[choice].nodes[b])
    return QueryPlan(replica_for_block=rep, index_scan=is_idx, nodes=nodes)


# ---------------------------------------------------------------------------
# Record readers (jit'd, batched over blocks)
# ---------------------------------------------------------------------------


# lo/hi are TRACED: ten different query ranges = one compilation.
@functools.partial(jax.jit, static_argnames=("partition_size",))
def _index_read(sorted_key, mins, bad, lo, hi, *, partition_size: int):
    f = jax.vmap(lambda k, m, b: idx.index_scan_mask(k, m, lo, hi,
                                                     partition_size) & ~b)
    mask = f(sorted_key, mins, bad)
    g = jax.vmap(lambda m: idx.rows_read_fraction(m, lo, hi, partition_size,
                                                  sorted_key.shape[1]))
    return mask, g(mins)


@jax.jit
def _full_read(key_col, bad, lo, hi):
    return jax.vmap(lambda k, b: idx.full_scan_mask(k, lo, hi) & ~b)(key_col, bad)


@dataclasses.dataclass
class ReadResult:
    """Fixed-shape result: projected columns + qualifying mask."""
    cols: dict[str, jax.Array]     # col -> (n_blocks, rows)
    mask: jax.Array                # (n_blocks, rows) bool
    rows_read_frac: jax.Array      # (n_blocks,) I/O model input
    bytes_read: "int | jax.Array"  # modeled bytes (index scan reads less);
    # may be a LAZY 0-d array so building a ReadResult never forces a
    # device sync — run_job materializes it at the completion barrier


@functools.partial(jax.jit, static_argnames=("rows",))
def _tail_mask(n_bad, *, rows: int):
    """(blocks, rows) bool: the last ``n_bad[b]`` rows of each block."""
    return (jnp.arange(rows, dtype=jnp.int32)[None, :]
            >= rows - n_bad[:, None])


def _bad_mask(store: BlockStore, replica: int) -> jax.Array:
    """Bad rows sit at the tail of INDEXED blocks (sorted there); for a
    block that is still unindexed they stay at their original upload
    positions — under adaptive indexing one replica mixes both, per block.
    Cached per (store, replica); ``commit_block_indexes`` invalidates the
    entry when a job flips blocks from upload order to sorted."""
    cache = store.__dict__.setdefault("_bad_mask_cache", {})
    if replica in cache:
        return cache[replica]
    rep = store.replicas[replica]
    rows = store.rows_per_block
    if store.devices:
        # placed: every replica is indexed; each chip makes its own blocks'
        m = rep.mins.map_parts(lambda part, blocks: _tail_mask(
            jax.device_put(store.bad_counts[blocks], part.sharding),
            rows=rows))
        cache[replica] = m
        return m
    orig = (store.bad_original if store.bad_original is not None
            else jnp.zeros((store.n_blocks, rows), bool))
    if rep.sort_key is None:
        m = orig
    else:
        tail = _tail_mask(store.bad_counts, rows=rows)
        if rep.indexed.all():
            m = tail
        else:
            m = jnp.where(jnp.asarray(rep.indexed)[:, None], tail, orig)
    cache[replica] = m
    return m


def _verify_replica_blocks(store: BlockStore, rid: int, bsel, names):
    """Read-path integrity gate for one replica's blocks (§3.2: HDFS always
    verifies chunk checksums on read; HAIL keeps that working with
    per-replica checksums).  Verifies exactly the columns this read will
    touch in ONE batched device dispatch, plus root-directory consistency
    (mins re-derived from the now-verified key column) for indexed blocks
    when the read uses the index.  Raises ``CorruptBlockError`` carrying the
    first failing (replica, block, col) — the executor quarantines it and
    re-plans.  Gated by ``store.verify_reads``; callers on the cached path
    invoke this only on BlockCache FILLS, so hits pay nothing."""
    if not store.verify_reads or store.layout != "pax":
        return
    from repro.kernels import ops
    rep = store.replicas[rid]
    names = tuple(dict.fromkeys(names))
    bsel = np.asarray(bsel)
    data = jnp.stack([rep.cols[c][bsel] for c in names])
    sums = jnp.stack([rep.checksums[c][bsel] for c in names])
    ok = np.asarray(ops.verify_blocks(data, sums))
    if not ok.all():
        ci, bi = np.argwhere(~ok)[0]
        ops.DISPATCH_COUNTS["verify_failures"] += 1
        b = int(bsel[bi])
        raise CorruptBlockError(rid, b, names[ci], int(rep.nodes[b]))
    if rep.sort_key in names:
        isel = np.asarray(rep.indexed[bsel], bool)
        if isel.any():
            sub = bsel[isel]
            rok = np.asarray(ops.verify_root(
                rep.mins[sub], rep.cols[rep.sort_key][sub],
                partition_size=store.partition_size))
            if not rok.all():
                ops.DISPATCH_COUNTS["verify_failures"] += 1
                b = int(sub[np.argwhere(~rok)[0][0]])
                raise CorruptBlockError(rid, b, "__root__",
                                        int(rep.nodes[b]))


def read_hail(store: BlockStore, query: HailQuery, qplan: QueryPlan,
              block_ids: Sequence[int] | None = None) -> ReadResult:
    """HAIL record reader over (a subset of) blocks, per-replica batched.

    Assembly is GATHER-based: per-replica batches are concatenated in
    replica order and restored to input order with one inverse-permutation
    take per array — no per-group ``.at[sel].set`` scatters on the hot path.
    """
    nb = store.n_blocks
    ids = np.arange(nb) if block_ids is None else np.asarray(block_ids)
    rows = store.rows_per_block
    proj_cols = query.projection + (ROWID,)
    if len(ids) == 0:                # degenerate split: empty fixed-shape result
        tmpl = store.template_replica()
        return ReadResult(
            cols={c: jnp.zeros((0, rows), tmpl.cols[c].dtype)
                  for c in proj_cols},
            mask=jnp.zeros((0, rows), bool),
            rows_read_frac=jnp.zeros((0,), jnp.float32), bytes_read=0)
    from repro.kernels import ops
    col_bytes = 4 * rows
    bytes_read = jnp.zeros((), jnp.float32)   # lazy: no sync at dispatch
    order: list[np.ndarray] = []     # input positions, concatenation order
    masks, fracs = [], []
    cols_parts: dict[str, list] = {c: [] for c in proj_cols}
    for rid in np.unique(qplan.replica_for_block[ids]):
        sel = np.nonzero(qplan.replica_for_block[ids] == rid)[0]
        bsel = ids[sel]
        rep = store.replicas[int(rid)]
        _verify_replica_blocks(
            store, int(rid), bsel,
            (proj_cols if query.filter is None
             else (query.filter[0],) + proj_cols))
        bad = _bad_mask(store, int(rid))[bsel]
        use_index = bool(qplan.index_scan[bsel].all()) and query.filter is not None
        if query.filter is not None:
            kind = "index_scan_blocks" if use_index else "full_scan_blocks"
            ops.DISPATCH_COUNTS[kind] += len(bsel)
            col, lo, hi = query.filter
            # per-column attribution: reader_stats + the store's AccessLog
            # (the governor's LRU eviction signal)
            gov.attribute_read(store, int(rid), col,
                               len(bsel) if use_index else 0,
                               0 if use_index else len(bsel))
            if use_index:
                m, fr = _index_read(rep.cols[col][bsel], rep.mins[bsel], bad,
                                    lo, hi,
                                    partition_size=store.partition_size)
                fr = fr.astype(jnp.float32)
            else:
                m = _full_read(rep.cols[col][bsel], bad, lo, hi)
                fr = jnp.ones((len(bsel),), jnp.float32)
        else:
            m = ~bad
            fr = jnp.ones((len(bsel),), jnp.float32)
        # modeled I/O: filter column read per partition range; projected
        # columns read for qualifying partitions only (PAX pruning)
        bytes_read += fr.sum() * col_bytes * (1 + len(query.projection))
        order.append(sel)
        masks.append(m)
        fracs.append(fr)
        for c in proj_cols:
            cols_parts[c].append(rep.cols[c][bsel])
    inv = np.empty(len(ids), dtype=np.int64)
    inv[np.concatenate(order)] = np.arange(len(ids))
    if len(order) == 1:              # single replica: concat+gather is a noop
        mask, frac = masks[0], fracs[0]
        out_cols = {c: v[0] for c, v in cols_parts.items()}
    else:
        mask = jnp.concatenate(masks, axis=0)[inv]
        frac = jnp.concatenate(fracs, axis=0)[inv]
        out_cols = {c: jnp.concatenate(v, axis=0)[inv]
                    for c, v in cols_parts.items()}
    return ReadResult(cols=out_cols, mask=mask, rows_read_frac=frac,
                      bytes_read=bytes_read)


def _gather_replica_inputs(store: BlockStore, rid: int, bsel: np.ndarray,
                           col: str, proj_cols: tuple):
    """Decoded reader inputs for one replica's blocks: (keys, stacked
    projection, bad mask, root directories).

    When the store carries a hot-block cache (``core/cache.BlockCache``,
    attached by the HailServer) the gathered device arrays are served from
    it — this host-side gather + stack is exactly the per-read work the
    cache removes for hot splits.  The cache is invalidated per replica by
    ``commit_block_indexes`` / ``demote_replica``, so a hit can never
    observe a half-committed replica."""
    cache = store.block_cache
    key = (rid, tuple(int(b) for b in bsel), col, proj_cols)
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            return hit
    rep = store.replicas[rid]
    # verify on FILL, not on hit: cached gathers are separate device arrays
    # already proven against the stored checksums, so hot splits pay zero
    # verification cost (the clean-path overhead bound in bench_fault)
    with obs_trace.span("cache_fill", track="cache",
                        args={"replica": rid, "blocks": len(bsel)}):
        _verify_replica_blocks(store, rid, bsel, (col,) + proj_cols)
        val = (rep.cols[col][bsel],
               jnp.stack([rep.cols[c][bsel] for c in proj_cols], axis=1),
               _bad_mask(store, rid)[bsel],
               rep.mins[bsel])
    if cache is not None:
        cache.put(key, val)
    return val


def _gather_split_inputs(store: BlockStore, qplan: QueryPlan,
                         ids: np.ndarray, col: str, proj_cols: tuple,
                         n_queries: int = 1):
    """Per-block kernel inputs for a split, replica-batched and restored to
    input order with one inverse-permutation take per array (no per-group
    ``.at[sel].set`` scatters on the hot path) — shared by the single-query
    and shared-scan fused readers.

    Attribution: each replica group is charged ``n_queries`` reads (one per
    query sharing the scan) through ``governor.attribute_read`` — cached or
    not, batched or not, the governor's AccessLog sees the same totals as
    ``n_queries`` serial jobs."""
    rids = qplan.replica_for_block[ids]
    order, keys_p, proj_p, bad_p, mins_p, uidx_p = [], [], [], [], [], []
    for rid in np.unique(rids):
        sel = np.nonzero(rids == rid)[0]
        bsel = ids[sel]
        n_idx = int(np.asarray(qplan.index_scan[bsel], bool).sum())
        for _ in range(n_queries):
            gov.attribute_read(store, int(rid), col, n_idx,
                               len(bsel) - n_idx)
        k, p, b, m = _gather_replica_inputs(store, int(rid), bsel, col,
                                            proj_cols)
        order.append(sel)
        keys_p.append(k)
        proj_p.append(p)
        bad_p.append(b)
        mins_p.append(m)
        uidx_p.append(np.asarray(qplan.index_scan[bsel], np.int32))
    inv = np.empty(len(ids), dtype=np.int64)
    inv[np.concatenate(order)] = np.arange(len(ids))
    if len(order) == 1:              # single replica: concat+gather is a noop
        return (mins_p[0], keys_p[0], proj_p[0], bad_p[0], uidx_p[0])
    return (jnp.concatenate(mins_p, axis=0)[inv],
            jnp.concatenate(keys_p, axis=0)[inv],
            jnp.concatenate(proj_p, axis=0)[inv],
            jnp.concatenate(bad_p, axis=0)[inv],
            np.concatenate(uidx_p, axis=0)[inv])


def _gather_traced(store: BlockStore, qplan: QueryPlan, ids: np.ndarray,
                   col: str, proj_cols: tuple, n_queries: int):
    """``_gather_split_inputs`` of a shared scan inside a ``gather`` span,
    whose arguments count the split's block-cache hits and misses."""
    cache = store.block_cache
    with obs_trace.span("gather", track="server") as args:
        if args is None or cache is None:
            return _gather_split_inputs(store, qplan, ids, col, proj_cols,
                                        n_queries)
        h0, m0 = cache.stats.hits, cache.stats.misses
        out = _gather_split_inputs(store, qplan, ids, col, proj_cols,
                                   n_queries)
        args.update(cache_hits=cache.stats.hits - h0,
                    cache_misses=cache.stats.misses - m0)
        return out


def attribution_groups(qplan: QueryPlan, block_ids: Sequence[int]
                       ) -> tuple[tuple[int, int, int], ...]:
    """The per-replica (replica_id, index-scanned, full-scanned) block
    counts ``_gather_split_inputs`` charges ONE query for this split — the
    result cache stores this recipe with each materialized answer and the
    server replays it through ``governor.attribute_read`` on every hit, so
    cached traffic and scanned traffic feed the AccessLog identically."""
    ids = np.asarray(block_ids)
    rids = qplan.replica_for_block[ids]
    out = []
    for rid in np.unique(rids):
        bsel = ids[rids == rid]
        n_idx = int(np.asarray(qplan.index_scan[bsel], bool).sum())
        out.append((int(rid), n_idx, len(bsel) - n_idx))
    return tuple(out)


def _empty_read(store: BlockStore, proj_cols: tuple,
                rows: int) -> ReadResult:
    """Degenerate split: empty fixed-shape result."""
    tmpl = store.template_replica()
    return ReadResult(
        cols={c: jnp.zeros((0, rows), tmpl.cols[c].dtype)
              for c in proj_cols},
        mask=jnp.zeros((0, rows), bool),
        rows_read_frac=jnp.zeros((0,), jnp.float32), bytes_read=0)


def read_hail_kernels(store: BlockStore, query: HailQuery, qplan: QueryPlan,
                      block_ids: Sequence[int] | None = None) -> ReadResult:
    """Kernel-backed record reader: ONE fused ``hail_read`` pallas_call per
    split (interpret mode on CPU), regardless of block count or replica mix.

    The kernel reads each block's root directory, prunes row tiles outside
    the qualifying partition range (per-block ``use_index`` selects pruned
    index scan vs failover full scan), and masks bad rows — so mixed-replica
    splits and the per-block retry splits ``run_job`` re-plans after a node
    failure all go through the same single dispatch.  Semantics identical to
    read_hail — asserted end-to-end by tests/test_kernels.py and
    tests/test_fused_reader.py."""
    from repro.kernels import ops

    assert query.filter is not None and store.layout == "pax"
    col, lo, hi = query.filter
    ids = (np.arange(store.n_blocks) if block_ids is None
           else np.asarray(block_ids))
    rows = store.rows_per_block
    proj_cols = tuple(query.projection) + (ROWID,)
    if len(ids) == 0:
        return _empty_read(store, proj_cols, rows)

    mins, keys, proj, bad, uidx = _gather_split_inputs(store, qplan, ids,
                                                       col, proj_cols)
    # one dispatch for the whole split; lo/hi are runtime scalars; uidx
    # stays a host array so ops' scan-mode counters cost no device sync
    mask, out, frac = ops.hail_read(mins, keys, proj, bad, uidx,
                                    lo, hi,
                                    partition_size=store.partition_size)
    cols = {c: out[:, j] for j, c in enumerate(proj_cols)}
    col_bytes = 4 * rows
    return ReadResult(cols=cols, mask=mask, rows_read_frac=frac,
                      bytes_read=frac.sum() * col_bytes
                      * (1 + len(query.projection)))


def read_hail_batch(store: BlockStore, queries: Sequence[HailQuery],
                    qplan: QueryPlan,
                    block_ids: Sequence[int] | None = None
                    ) -> tuple[list[ReadResult], "int | jax.Array"]:
    """SHARED-SCAN record reader: ONE fused pallas_call serves a whole batch
    of compatible queries (same filter column, same projection, same plan)
    over a split — Q concurrent range queries cost one dispatch and one
    pass over the data instead of Q (the HailServer's hot path).

    Returns (one ReadResult per query, shared physical bytes).  The per-
    query results carry that query's own mask and rows-read fraction; the
    projection columns are SHARED device arrays masked by the union of the
    batch's masks, which is exact under each query's own mask (``collect``
    touches only mask-true rows).  The second return value models the
    PHYSICAL I/O of the shared scan — per block, the widest partition range
    any query in the batch needed (a lazy 0-d array; no sync at dispatch).
    The one program returns every one of these arrays already split per
    query and column, so building the results launches no device program.
    """
    from repro.kernels import ops

    assert store.layout == "pax" and len(queries) >= 1
    col = queries[0].filter_col
    assert col is not None, "shared-scan batches need a range filter"
    proj = tuple(queries[0].projection)
    for qq in queries[1:]:
        assert qq.filter_col == col and tuple(qq.projection) == proj, \
            "batched queries must share filter column and projection"
    ids = (np.arange(store.n_blocks) if block_ids is None
           else np.asarray(block_ids))
    rows = store.rows_per_block
    proj_cols = proj + (ROWID,)
    if len(ids) == 0:
        return [_empty_read(store, proj_cols, rows) for _ in queries], 0

    mins, keys, proj_arr, bad, uidx = _gather_traced(
        store, qplan, ids, col, proj_cols, len(queries))
    lohi = np.asarray([[qq.filter[1], qq.filter[2]] for qq in queries],
                      np.int32)
    with obs_trace.span("issue", track="server") as args:
        if args is not None:
            n_idx = int(uidx.astype(bool).sum())
            args.update(queries=len(queries), index_blocks=n_idx,
                        full_blocks=len(ids) - n_idx,
                        chip=store.chip_of(qplan.nodes[ids[0]]))
        read = ops.hail_read_batch_split(
            mins, keys, proj_arr, bad, uidx, lohi,
            partition_size=store.partition_size)
    cols = dict(zip(proj_cols, read.cols))
    results = [ReadResult(cols=cols, mask=m, rows_read_frac=f, bytes_read=b)
               for m, f, b in zip(read.masks, read.fracs, read.bytes_read)]
    return results, read.shared_bytes


def gather_shared_scan_inputs(store: BlockStore,
                              queries: Sequence[HailQuery],
                              qplan: QueryPlan,
                              block_ids: Sequence[int]):
    """Pre-gathered fused-reader inputs for ONE split of a (possibly
    sharded) shared scan: (mins, keys, proj, bad, use_index).

    This is the host-side half of the fused read — BlockCache traffic,
    read-path checksum verification (raising ``CorruptBlockError`` exactly
    like the unsharded readers, so executors keep their quarantine/re-plan
    handling per split), and governor attribution all happen HERE; the wave
    executor then ships many splits' inputs in one sharded dispatch."""
    ids = np.asarray(block_ids)
    col = queries[0].filter_col
    assert col is not None and store.layout == "pax"
    proj_cols = tuple(queries[0].projection) + (ROWID,)
    return _gather_traced(store, qplan, ids, col, proj_cols, len(queries))


def read_hail_batch_sharded(store: BlockStore,
                            queries: Sequence[HailQuery],
                            gathered: Sequence[tuple], mesh, axes
                            ) -> list[tuple[list[ReadResult],
                                            "int | jax.Array"]]:
    """SHARDED shared-scan reader: ONE shard_map'd fused dispatch serves a
    WAVE of up to n_dev splits, each split's block tile scanned on its own
    device against the batch's replicated (Q, 2) ranges.

    ``gathered`` holds per-split inputs from ``gather_shared_scan_inputs``
    (1 <= len <= n_dev).  Ragged splits are padded to the wave's max block
    count with DEAD blocks (bad=True rows — the kernel masks them to
    False) and the wave is padded to n_dev splits, so every device runs
    the identical program; outputs are sliced back per split, making the
    row-sets byte-identical to len(gathered) single-device dispatches.
    Returns one (results-per-query, shared_bytes) pair per split, shaped
    exactly like ``read_hail_batch``'s return value.
    """
    from repro.kernels import ops
    from repro.dist import sharding as dsh

    assert store.layout == "pax" and len(queries) >= 1
    col = queries[0].filter_col
    assert col is not None, "shared-scan batches need a range filter"
    proj = tuple(queries[0].projection)
    proj_cols = proj + (ROWID,)
    rows = store.rows_per_block
    col_bytes = 4 * rows
    n_dev = dsh.scan_device_count(mesh, axes)
    n_splits = len(gathered)
    assert 1 <= n_splits <= n_dev, (n_splits, n_dev)
    n_q = len(queries)
    lohi = np.asarray([[qq.filter[1], qq.filter[2]] for qq in queries],
                      np.int32)

    sizes = [int(g[0].shape[0]) for g in gathered]
    bmax = max(sizes)
    # scan-mode counters over REAL blocks only (padding must not skew the
    # serial-equivalent accounting); the sharded ops wrapper counts waves
    for g in gathered:
        u = np.asarray(g[4])
        n_idx = int(u.astype(bool).sum())
        ops.DISPATCH_COUNTS["index_scan_blocks"] += n_q * n_idx
        ops.DISPATCH_COUNTS["full_scan_blocks"] += n_q * (u.shape[0] - n_idx)

    def _pad(g):
        mins, keys, proj_a, bad, uidx = g
        extra = bmax - mins.shape[0]
        if extra == 0:
            return mins, keys, proj_a, bad, np.asarray(uidx, np.int32)
        return (jnp.concatenate(
                    [mins, jnp.zeros((extra,) + mins.shape[1:], mins.dtype)]),
                jnp.concatenate(
                    [keys, jnp.zeros((extra,) + keys.shape[1:], keys.dtype)]),
                jnp.concatenate(
                    [proj_a,
                     jnp.zeros((extra,) + proj_a.shape[1:], proj_a.dtype)]),
                jnp.concatenate(
                    [bad, jnp.ones((extra,) + bad.shape[1:], bool)]),
                np.concatenate([np.asarray(uidx, np.int32),
                                np.zeros((extra,), np.int32)]))

    padded = [_pad(g) for g in gathered]
    while len(padded) < n_dev:        # dead dummy splits fill the mesh
        mins0, keys0, proj0, bad0, _ = padded[0]
        padded.append((jnp.zeros_like(mins0), jnp.zeros_like(keys0),
                       jnp.zeros_like(proj0), jnp.ones_like(bad0),
                       np.zeros((bmax,), np.int32)))
    mins = jnp.concatenate([p[0] for p in padded], axis=0)
    keys = jnp.concatenate([p[1] for p in padded], axis=0)
    proj_arr = jnp.concatenate([p[2] for p in padded], axis=0)
    bad = jnp.concatenate([p[3] for p in padded], axis=0)
    uidx = np.concatenate([p[4] for p in padded], axis=0)

    mask, out, frac = ops.hail_read_batch_sharded(
        mins, keys, proj_arr, bad, uidx, lohi,
        partition_size=store.partition_size, mesh=mesh, axes=axes,
        n_splits=n_splits)

    outs = []
    for s in range(n_splits):
        sl = slice(s * bmax, s * bmax + sizes[s])
        cols = {c: out[sl, j] for j, c in enumerate(proj_cols)}
        m, fr = mask[sl], frac[sl]
        results = [
            ReadResult(cols=cols, mask=m[:, qi],
                       rows_read_frac=fr[:, qi],
                       bytes_read=fr[:, qi].sum() * col_bytes
                       * (1 + len(proj)))
            for qi in range(n_q)]
        shared = fr.max(axis=1).sum() * col_bytes * (1 + len(proj))
        outs.append((results, shared))
    return outs


@functools.lru_cache(maxsize=None)
def _hadoop_reader(schema, filter_col, projection):
    """Compiled parse+scan for (schema, filter col, projection) — (lo, hi)
    and the data are traced, so the parser compiles once per job SHAPE, not
    once per split per query (the seed rebuilt the jit closure per call)."""

    @jax.jit
    def go(raw, bids, lo, hi):
        def one(block, bid):
            cols, bad = ps.parse_block(schema, block)
            cols[ROWID] = (bid * block.shape[0]
                           + jnp.arange(block.shape[0], dtype=jnp.int32))
            if filter_col is not None:
                m = idx.full_scan_mask(cols[filter_col], lo, hi) & ~bad
            else:
                m = ~bad
            return {c: cols[c] for c in projection + (ROWID,)}, m

        return jax.vmap(one)(raw, bids)

    return go


def read_hadoop(store: BlockStore, query: HailQuery,
                block_ids: Sequence[int] | None = None) -> ReadResult:
    """Hadoop baseline: parse raw ASCII rows, then scan (row layout)."""
    assert store.layout == "row_ascii"
    ids = (np.arange(store.n_blocks) if block_ids is None
           else np.asarray(block_ids))
    raw = store.replicas[0].cols["__raw__"][ids]

    go = _hadoop_reader(store.schema, query.filter_col, query.projection)
    if query.filter is not None:
        _, lo, hi = query.filter
    else:
        lo = hi = 0
    cols, mask = go(raw, jnp.asarray(ids, jnp.int32),
                    jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))
    return ReadResult(cols=cols, mask=mask,
                      rows_read_frac=jnp.ones((len(ids),)),
                      bytes_read=int(raw.size))


def collect(result: ReadResult) -> dict[str, np.ndarray]:
    """Materialize qualifying rows (host side, for tests/examples)."""
    m = np.asarray(result.mask).reshape(-1)
    return {c: np.asarray(v).reshape(-1)[m] for c, v in result.cols.items()}
