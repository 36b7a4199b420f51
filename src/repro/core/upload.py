"""Upload pipelines: HAIL vs HDFS(Hadoop) vs Hadoop++ (paper §3, §6.3).

HAIL (one pass, everything piggy-backed):
  parse ASCII -> binary PAX once on the client, then per replica r:
  sort by key_r (bad records to the tail) -> gather all columns ->
  build sparse root index -> recompute per-replica checksums.
  No re-read of the data: the sort/index ride the upload pipeline.

Hadoop (HDFS): store the raw ASCII block R times + chunk checksums.  No
parse, no index — query time pays the full parse+scan.

Hadoop++: Hadoop upload first, THEN an extra MapReduce job re-reads every
replica, parses, sorts by ONE global key and rewrites + re-checksums —
the extra read+write per replica the paper charges it with (§5).

All pipelines are jit'd per-block tensor programs vmapped over blocks, so
measured wall-clock ratios are real compute ratios; byte counts feed the
disk/network model in the benchmarks.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checksum as ck
from repro.core import index as idx
from repro.core import parse as ps
from repro.core.schema import ROWID, Schema
from repro.core.store import (BlockStore, Namenode, PlacedBlocks, Replica,
                              ReplicaInfo, assign_nodes, replica_key_ranges)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def _note_upload(kind: str, t0: float, stats: UploadStats):
    """Fold one finished upload into the flight recorder: an X slice per
    measured phase on the upload track plus the registry counters."""
    start = t0
    for phase, wall in stats.phases.items():
        obs_trace.complete_wall(f"upload:{phase}", start, wall,
                                track="upload",
                                args={"kind": kind,
                                      "ascii_bytes": stats.ascii_bytes,
                                      "written_bytes": stats.written_bytes})
        start += wall
    obs_metrics.observe_upload(kind, stats)


@dataclasses.dataclass
class UploadStats:
    wall_s: float                 # measured compute; == sum(phases.values())
    ascii_bytes: int              # bytes received by the client
    written_bytes: int            # bytes written across all replicas
    extra_read_bytes: int = 0     # Hadoop++ post-hoc job re-reads (modeled
    #   I/O — charged ONCE, by the disk model, never also as compute wall)
    n_indexes: int = 0
    phases: dict = dataclasses.field(default_factory=dict)
    # ^ explicit per-phase measured walls, e.g. {"hdfs": ..,
    #   "trojan_rewrite": ..} — see EXPERIMENTS.md


# ---------------------------------------------------------------------------
# HAIL
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _hail_pipeline(schema: Schema, sort_keys: tuple, partition_size: int):
    """Cached jit wrapper per (schema, keys, partition) — repeat uploads of
    the same shape reuse the compiled pipeline, so warm-up calls actually
    warm and measured upload walls compare compute, not trace+compile."""
    return jax.jit(jax.vmap(
        functools.partial(_hail_block, schema, sort_keys=sort_keys,
                          partition_size=partition_size)))


@functools.lru_cache(maxsize=None)
def _lazy_pipeline(schema: Schema):
    return jax.jit(jax.vmap(functools.partial(_lazy_block, schema)))


# home blocks a chip parses in one pass of a placed upload: parsing 8
# blocks of 2^19 UserVisits rows takes about 1.5 GB of temporaries on a
# v5e (16 blocks: 3.1 GB), beside a chip's 3 GB of placed PAX and its
# 2.3 GB of text
PASS_BLOCKS = 8


@functools.lru_cache(maxsize=None)
def _parse_pipeline(schema: Schema):
    return jax.jit(jax.vmap(functools.partial(_parse_rows, schema)))


@functools.lru_cache(maxsize=None)
def _index_pipeline(partition_size: int):
    """One program for every replica: the sort key is an argument."""
    return jax.jit(jax.vmap(functools.partial(
        _index_replica, partition_size=partition_size)))


def _parse_rows(schema: Schema, raw, block_id):
    """The HAIL client's step for one block: raw (rows, row_width) u8 ->
    PAX columns plus the global row id, and the bad-record mask."""
    cols, bad = ps.parse_block(schema, raw)
    cols[ROWID] = (block_id * raw.shape[0]
                   + jnp.arange(raw.shape[0], dtype=jnp.int32))
    return cols, bad


def _index_replica(cols, bad, key_col, partition_size):
    """A datanode's step for one replica of a parsed block: sort on its key
    column ``key_col`` (bad records to the tail; ``None`` keeps upload
    order), root directory, checksums over the replica's own order."""
    rows = bad.shape[0]
    if key_col is None:
        perm = jnp.arange(rows, dtype=jnp.int32)
        mins = jnp.zeros((rows // partition_size,), jnp.int32)
    else:
        perm = idx.sort_permutation(key_col, bad)
        mins = idx.build_root(key_col[perm], partition_size)
    sorted_cols = {k: v[perm] for k, v in cols.items()}
    return sorted_cols, mins, ck.block_checksums(sorted_cols)


def _hail_block(schema: Schema, raw, block_id, sort_keys, partition_size):
    """Per-block pipeline; raw (rows, row_width) u8."""
    cols, bad = _parse_rows(schema, raw, block_id)
    return [_index_replica(cols, bad, None if key is None else cols[key],
                           partition_size)
            for key in sort_keys], bad


def hail_upload(schema: Schema, raw_blocks: np.ndarray,
                sort_keys: Optional[Sequence[Optional[str]]] = None,
                partition_size: int = idx.PARTITION,
                n_nodes: int = 10, *,
                index_columns: Optional[Sequence[str]] = None,
                replication: Optional[int] = None,
                devices: Optional[Sequence] = None
                ) -> tuple[BlockStore, UploadStats]:
    """raw_blocks (n_blocks, rows, row_width) uint8.

    ``devices``: the chips of a PLACED store (more than one): replica r of
    block b is made on, and stays on, ``devices[nodes[r, b] % len(devices)]``
    (``placed_upload``).  One chip, or none, gives the one-chip store.

    ``sort_keys`` (alias ``index_columns``): one entry per replica; ``None``
    entries ship that replica unindexed.  The EMPTY sequence
    (``index_columns=()``) is the LAZY fast path: parse + checksum once,
    replicate ``replication`` times (default 3) with NO sort and NO index —
    blocks are indexed later, incrementally, by adaptive jobs
    (``run_job(adaptive=AdaptiveConfig(...))``).  With non-empty keys the
    replica count IS ``len(sort_keys)``; a conflicting ``replication`` is
    rejected rather than silently ignored.
    """
    if index_columns is not None:
        sort_keys = index_columns
    assert sort_keys is not None, "pass sort_keys or index_columns"
    sort_keys = tuple(sort_keys)
    if len(sort_keys) == 0:
        return hail_lazy_upload(schema, raw_blocks,
                                3 if replication is None else replication,
                                partition_size, n_nodes)
    if replication is not None and replication != len(sort_keys):
        raise ValueError(
            f"replication={replication} conflicts with {len(sort_keys)} "
            f"sort_keys — replica count is len(sort_keys) on the eager path")
    if devices is not None and len(devices) > 1:
        return placed_upload(schema, raw_blocks, sort_keys, partition_size,
                             n_nodes, tuple(devices))
    n_blocks, rows, width = raw_blocks.shape
    fn = _hail_pipeline(schema, sort_keys, partition_size)
    t0 = time.perf_counter()
    reps, bad = fn(jnp.asarray(raw_blocks),
                   jnp.arange(n_blocks, dtype=jnp.int32))
    jax.block_until_ready(reps)
    wall = time.perf_counter() - t0
    bad_counts = np.asarray(bad.sum(axis=1), np.int32)

    nodes = assign_nodes(n_blocks, len(sort_keys), n_nodes)
    namenode = Namenode()
    replicas = []
    written = 0
    for r, (cols, mins, sums) in enumerate(reps):
        rep = Replica(sort_key=sort_keys[r], cols=cols, mins=mins,
                      checksums=sums, nodes=nodes[r])
        if rep.sort_key is not None:
            rep.key_range = replica_key_ranges(rep, bad_counts)
        replicas.append(rep)
        written += rep.nbytes
        per_block_bytes = rep.nbytes // n_blocks
        for b in range(n_blocks):
            namenode.register(ReplicaInfo(
                block_id=b, node=int(nodes[r, b]), sort_key=sort_keys[r],
                partition_size=partition_size, n_rows=rows, layout="pax",
                nbytes=per_block_bytes))
    store = BlockStore(schema=schema, n_blocks=n_blocks, rows_per_block=rows,
                       partition_size=partition_size, replicas=replicas,
                       bad_counts=bad_counts, namenode=namenode, layout="pax",
                       bad_original=bad)
    stats = UploadStats(wall_s=wall, ascii_bytes=raw_blocks.size,
                        written_bytes=written,
                        n_indexes=sum(k is not None for k in sort_keys),
                        phases={"hail": wall})
    _note_upload("hail", t0, stats)
    return store, stats


def _home_blocks(raw_blocks, devices: tuple) -> list:
    """(chip, first block, text) for each run of blocks and the chip it
    lives on: the shards of a ``jax.Array`` split over blocks, else the
    whole table put on the first chip."""
    if not isinstance(raw_blocks, jax.Array):
        return [(0, 0, jax.device_put(raw_blocks, devices[0]))]
    runs = {}
    for sh in raw_blocks.addressable_shards:
        if sh.device not in devices:
            raise ValueError(f"text block shard on {sh.device}, which is not "
                             f"one of the store's chips")
        first = (sh.index[0].start or 0) if sh.index else 0
        runs.setdefault(first, (devices.index(sh.device), first, sh.data))
    return [runs[k] for k in sorted(runs)]


def placed_upload(schema: Schema, raw_blocks, sort_keys: tuple,
                  partition_size: int, n_nodes: int, devices: tuple
                  ) -> tuple[BlockStore, UploadStats]:
    """HAIL's upload pipeline across chips, into a placed store.

    Text blocks are parsed once on the chip that holds them (the client's
    step), each block's unsorted PAX and bad mask are copied to the chips
    of its replicas (the datanode pipeline), and each of those chips sorts
    the block on its own replica's key, builds the root directory and
    recomputes the checksums (``_index_replica``).  The chip of replica r
    of block b is ``nodes[r, b] % len(devices)``, with ``assign_nodes``'
    placement.  It runs in passes of ``PASS_BLOCKS`` blocks a home chip, so
    a pass's temporaries stay small beside the store.  Every replica comes
    out bit-equal to ``hail_upload``'s on one device; row ids stay global.
    """
    if any(k is None for k in sort_keys):
        raise ValueError("a placed upload indexes every replica")
    n_blocks, rows, _ = raw_blocks.shape
    n_chips = len(devices)
    nodes = assign_nodes(n_blocks, len(sort_keys), n_nodes)
    chip = nodes % n_chips                       # (replicas, n_blocks)
    slot = np.zeros_like(chip)
    homes = _home_blocks(raw_blocks, devices)
    parse = _parse_pipeline(schema)
    pieces: dict = {}             # (replica, chip) -> [(cols, mins, sums)]
    filled = np.zeros((len(sort_keys), n_chips), np.int64)
    bad_counts = np.zeros((n_blocks,), np.int32)
    n_pass = max(-(-text.shape[0] // PASS_BLOCKS) for _, _, text in homes)
    t0 = time.perf_counter()
    for p in range(n_pass):
        parsed = []
        for h, first, text in homes:
            lo, hi = p * PASS_BLOCKS, min((p + 1) * PASS_BLOCKS,
                                          text.shape[0])
            if lo >= hi:
                continue
            ids = np.arange(first + lo, first + hi)
            with obs_trace.span("upload_parse", track="upload") as args:
                if args is not None:
                    args.update(chip=h, blocks=len(ids))
                cols, bad = parse(text[lo:hi], ids.astype(np.int32))
            parsed.append((ids, cols, bad))
        done = []
        for k, dev in enumerate(devices):
            got_ids, got = [], []
            for ids, cols, bad in parsed:
                sel = np.flatnonzero((chip[:, ids] == k).any(axis=0))
                if not len(sel):
                    continue
                piece = ({c: v[sel] for c, v in cols.items()}, bad[sel])
                with obs_trace.span("upload_ship", track="upload") as args:
                    if args is not None:
                        args.update(chip=k, bytes=sum(
                            a.nbytes for a in jax.tree.leaves(piece)))
                    got.append(jax.device_put(piece, dev))
                got_ids.append(ids[sel])
            if not got:
                continue
            ids_k = np.concatenate(got_ids)
            cols_k = {c: jnp.concatenate([g[0][c] for g in got])
                      for c in got[0][0]}
            bad_k = jnp.concatenate([g[1] for g in got])
            for r, key in enumerate(sort_keys):
                mine = np.flatnonzero(chip[r, ids_k] == k)
                if not len(mine):
                    continue
                with obs_trace.span("upload_index", track="upload") as args:
                    if args is not None:
                        args.update(chip=k, replica=r, blocks=len(mine))
                    mine_cols = {c: v[mine] for c, v in cols_k.items()}
                    out = _index_pipeline(partition_size)(
                        mine_cols, bad_k[mine], mine_cols[key])
                slot[r, ids_k[mine]] = filled[r, k] + np.arange(len(mine))
                filled[r, k] += len(mine)
                pieces.setdefault((r, k), []).append(out)
                done.append(out)
        for ids, _, bad in parsed:
            bad_counts[ids] = np.asarray(bad.sum(axis=1))
        jax.block_until_ready(done)
    wall = time.perf_counter() - t0

    def placed(r, get):
        """Replica r's array ``get(piece)``: on each chip its pieces joined,
        in slot order."""
        tmpl = get(next(pc for (rr, _), v in pieces.items() if rr == r
                        for pc in v))
        return PlacedBlocks(tuple(
            jnp.concatenate([get(pc) for pc in pieces[(r, k)]])
            if (r, k) in pieces else jax.device_put(
                np.zeros((0,) + tmpl.shape[1:], tmpl.dtype), dev)
            for k, dev in enumerate(devices)), chip[r], slot[r])

    namenode = Namenode()
    replicas = []
    for r, key in enumerate(sort_keys):
        names = list(next(v for (rr, _), v in pieces.items() if rr == r)[0][0])
        cols = {c: placed(r, lambda pc, c=c: pc[0][c]) for c in names}
        rep = Replica(sort_key=key, cols=cols,
                      mins=placed(r, lambda pc: pc[1]),
                      checksums={c: placed(r, lambda pc, c=c: pc[2][c])
                                 for c in names},
                      nodes=nodes[r])
        rep.key_range = replica_key_ranges(rep, bad_counts)
        for k in range(n_chips):
            pieces.pop((r, k), None)
        replicas.append(rep)
        for b in range(n_blocks):
            namenode.register(ReplicaInfo(
                block_id=b, node=int(nodes[r, b]), sort_key=key,
                partition_size=partition_size, n_rows=rows, layout="pax",
                nbytes=rep.nbytes // n_blocks))
    store = BlockStore(schema=schema, n_blocks=n_blocks, rows_per_block=rows,
                       partition_size=partition_size, replicas=replicas,
                       bad_counts=bad_counts, namenode=namenode, layout="pax",
                       devices=devices)
    stats = UploadStats(wall_s=wall, ascii_bytes=int(raw_blocks.size),
                        written_bytes=store.nbytes,
                        n_indexes=len(sort_keys), phases={"hail": wall})
    _note_upload("hail", t0, stats)
    return store, stats


def _lazy_block(schema: Schema, raw, block_id):
    """Per-block LAZY pipeline: parse + rowid + checksums — no sort/index."""
    cols, bad = _parse_rows(schema, raw, block_id)
    return cols, ck.block_checksums(cols), bad


def hail_lazy_upload(schema: Schema, raw_blocks: np.ndarray,
                     replication: int = 3,
                     partition_size: int = idx.PARTITION,
                     n_nodes: int = 10) -> tuple[BlockStore, UploadStats]:
    """Adaptive-HAIL upload (LIAH): ship PAX blocks UNINDEXED.

    One parse + one checksum pass serve all replicas (identical bytes until
    a replica is adaptively sorted), so upload pays neither the per-replica
    sort nor the index build — that work is earned back incrementally by
    ``run_job(adaptive=...)`` piggybacking on full-scan map tasks.  Replicas
    start unclaimed (``sort_key=None``, ``indexed`` all-False) with zeroed
    root directories sized for ``partition_size``; their host key ranges
    fill as ``commit_block_indexes`` lands.
    """
    n_blocks, rows, width = raw_blocks.shape
    fn = _lazy_pipeline(schema)
    t0 = time.perf_counter()
    cols, sums, bad = fn(jnp.asarray(raw_blocks),
                         jnp.arange(n_blocks, dtype=jnp.int32))
    jax.block_until_ready(bad)
    wall = time.perf_counter() - t0
    bad_counts = np.asarray(bad.sum(axis=1), np.int32)

    nodes = assign_nodes(n_blocks, replication, n_nodes)
    namenode = Namenode()
    replicas = []
    written = 0
    zero_mins = jnp.zeros((n_blocks, rows // partition_size), jnp.int32)
    for r in range(replication):
        # per-replica dicts (commit rebinds entries per replica); the column
        # arrays alias until an adaptive commit diverges them functionally
        rep = Replica(sort_key=None, cols=dict(cols), mins=zero_mins,
                      checksums=dict(sums), nodes=nodes[r])
        replicas.append(rep)
        written += rep.nbytes
        per_block_bytes = rep.nbytes // n_blocks
        for b in range(n_blocks):
            namenode.register(ReplicaInfo(
                block_id=b, node=int(nodes[r, b]), sort_key=None,
                partition_size=partition_size, n_rows=rows, layout="pax",
                nbytes=per_block_bytes))
    store = BlockStore(schema=schema, n_blocks=n_blocks, rows_per_block=rows,
                       partition_size=partition_size, replicas=replicas,
                       bad_counts=bad_counts, namenode=namenode, layout="pax",
                       bad_original=bad)
    stats = UploadStats(wall_s=wall, ascii_bytes=raw_blocks.size,
                        written_bytes=written, n_indexes=0,
                        phases={"hail_lazy": wall})
    _note_upload("hail_lazy", t0, stats)
    return store, stats


# ---------------------------------------------------------------------------
# Hadoop (plain HDFS)
# ---------------------------------------------------------------------------


def hdfs_upload(schema: Schema, raw_blocks: np.ndarray, replication: int = 3,
                n_nodes: int = 10) -> tuple[BlockStore, UploadStats]:
    """Raw ASCII replicated R times; checksums only (what HDFS computes)."""
    n_blocks, rows, width = raw_blocks.shape
    raw = jnp.asarray(raw_blocks)
    sums_fn = jax.jit(jax.vmap(ck.chunk_checksums))
    t0 = time.perf_counter()
    sums = sums_fn(raw.reshape(n_blocks, -1))
    jax.block_until_ready(sums)
    wall = time.perf_counter() - t0

    nodes = assign_nodes(n_blocks, replication, n_nodes)
    namenode = Namenode()
    replicas = []
    for r in range(replication):
        rep = Replica(sort_key=None, cols={"__raw__": raw}, mins=None,
                      checksums={"__raw__": sums}, nodes=nodes[r])
        replicas.append(rep)
        for b in range(n_blocks):
            namenode.register(ReplicaInfo(
                block_id=b, node=int(nodes[r, b]), sort_key=None,
                partition_size=0, n_rows=rows, layout="row_ascii",
                nbytes=rows * width))
    store = BlockStore(schema=schema, n_blocks=n_blocks, rows_per_block=rows,
                       partition_size=0, replicas=replicas,
                       bad_counts=np.zeros((n_blocks,), np.int32),
                       namenode=namenode, layout="row_ascii")
    stats = UploadStats(wall_s=wall, ascii_bytes=raw_blocks.size,
                        written_bytes=raw_blocks.size * replication,
                        phases={"hdfs": wall})
    _note_upload("hdfs", t0, stats)
    return store, stats


# ---------------------------------------------------------------------------
# Hadoop++ (trojan index: post-hoc MapReduce job, one global sort key)
# ---------------------------------------------------------------------------


def hadooppp_upload(schema: Schema, raw_blocks: np.ndarray, sort_key: str,
                    replication: int = 3, partition_size: int = idx.PARTITION,
                    n_nodes: int = 10) -> tuple[BlockStore, UploadStats]:
    # phase 1: plain HDFS upload (pays checksum pass over raw bytes)
    _, s1 = hdfs_upload(schema, raw_blocks, replication, n_nodes)
    # phase 2: the trojan-index MapReduce job re-reads every replica, parses,
    # sorts by the ONE key, rewrites every replica.  The REWRITE compute is
    # measured (the HAIL-style pipeline below); the RE-READ is disk I/O and
    # is charged exactly once, as ``extra_read_bytes`` through the disk
    # model (upload_model_seconds) — the seed double-counted it by timing a
    # simulated checksum re-read AND re-running the full upload's compute.
    keys = tuple([sort_key] * replication)
    store, s2 = hail_upload(schema, raw_blocks, keys, partition_size, n_nodes)
    phases = {"hdfs": s1.wall_s, "trojan_rewrite": s2.wall_s}
    stats = UploadStats(
        wall_s=sum(phases.values()),
        ascii_bytes=s1.ascii_bytes,
        written_bytes=s1.written_bytes + s2.written_bytes,
        extra_read_bytes=s1.written_bytes,  # job re-reads each replica
        n_indexes=1,
        phases=phases)
    obs_metrics.observe_upload("hadooppp", stats)
    return store, stats
