"""Replicated PAX block store + namenode metadata (paper §3.2-§3.3).

``BlockStore`` holds R physically different replicas of every logical block:
replica r is sorted by its own key with a sparse clustered index and its own
checksums (sort order differs => checksums differ, exactly as in the paper).
An implicit ``__rowid__`` column preserves logical row identity, so *any*
replica reconstructs the logical block (failover invariant).

``Namenode`` is the central directory: ``dir_block`` (blockID -> datanodes)
plus HAIL's addition ``dir_rep`` ((blockID, node) -> HAILBlockReplicaInfo)
used by the scheduler to route map tasks to matching indexes (§3.3, §4.3).

Adaptive indexing (LIAH, the paper's sequel) makes the store STATE-EVOLVING:
blocks may upload unindexed (``Replica.indexed`` all-False) and running jobs
commit per-block clustered indexes back via ``commit_block_indexes`` — the
replica's columns, root directory, host key ranges, checksums, per-block
index flags and the namenode's Dir_rep all advance together, and query-side
caches (the bad-row mask, any attached ``core/cache.BlockCache``) are
invalidated.  Planning reads this LIVE state, so repeated jobs
converge from all-full-scan to all-index-scan.

The index governor (core/governor.py) adds the REVERSE transition:
``demote_replica`` drops a replica's per-block indexes back to
``sort_key=None`` upload order — columns are un-sorted via the logical
``__rowid__`` column, the root directory zeroes, checksums are recomputed,
Dir_rep rewinds, the bad-mask cache invalidates — so a shifted workload can
re-claim and re-key the replica through the same claim/commit path.  When a
governor is attached (``store.governor``), ``commit_block_indexes`` also
enforces its storage budget as a hard backstop: commits are trimmed so the
total indexed-block count can never exceed the budget.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checksum as ck
from repro.core import index as idx
from repro.core.schema import ROWID, Schema


@dataclasses.dataclass(frozen=True)
class ReplicaInfo:
    """HAILBlockReplicaInfo: what the namenode knows about one replica."""
    block_id: int
    node: int
    sort_key: Optional[str]        # clustered-index key (None = unindexed)
    partition_size: int
    n_rows: int
    layout: str                    # 'pax' | 'row_ascii'
    nbytes: int


class Namenode:
    """Central metadata service (Dir_block + Dir_rep + liveness)."""

    def __init__(self):
        self.dir_block: dict[int, list[int]] = {}
        self.dir_rep: dict[tuple[int, int], ReplicaInfo] = {}
        self.dead: set[int] = set()
        # (block_id, node) pairs whose replica failed read-path checksum
        # verification — excluded from placement like a dead node, but at
        # BLOCK granularity, and reversible only by repair_blocks (never by
        # revive: a revived node's corrupt block is still corrupt)
        self.quarantined: set[tuple[int, int]] = set()

    def register(self, info: ReplicaInfo):
        self.dir_block.setdefault(info.block_id, []).append(info.node)
        self.dir_rep[(info.block_id, info.node)] = info

    def locate(self, block_id: int) -> list[int]:
        return [n for n in self.dir_block[block_id]
                if n not in self.dead
                and (block_id, n) not in self.quarantined]

    def quarantine(self, block_id: int, node: int):
        self.quarantined.add((block_id, node))

    def clear_quarantine(self, block_id: int, node: int):
        self.quarantined.discard((block_id, node))

    def is_quarantined(self, block_id: int, node: int) -> bool:
        return (block_id, node) in self.quarantined

    def replicas(self, block_id: int) -> list[ReplicaInfo]:
        return [self.dir_rep[(block_id, n)] for n in self.locate(block_id)]

    def get_hosts_with_index(self, block_id: int, key: str) -> list[int]:
        """The paper's new BlockLocation.getHostsWithIndex()."""
        return [r.node for r in self.replicas(block_id) if r.sort_key == key]

    def update_index(self, block_id: int, node: int,
                     sort_key: Optional[str]):
        """Adaptive-index commit (or governor demotion rewind): a running
        job built — or the governor dropped — a clustered index for this
        replica; advance/rewind Dir_rep so later planning sees it.
        ``sort_key=None`` rewinds the replica to unindexed."""
        info = self.dir_rep[(block_id, node)]
        self.dir_rep[(block_id, node)] = dataclasses.replace(
            info, sort_key=sort_key)

    def unregister(self, block_id: int, node: int):
        """Decommission: drop one replica's (block, node) registration —
        Dir_block, Dir_rep and any quarantine record for the pair."""
        nodes = self.dir_block.get(block_id, [])
        if node in nodes:
            nodes.remove(node)
        self.dir_rep.pop((block_id, node), None)
        self.quarantined.discard((block_id, node))

    def kill_node(self, node: int):
        self.dead.add(node)

    def revive(self, node: int | None = None):
        if node is None:
            self.dead.clear()
        else:
            self.dead.discard(node)


@dataclasses.dataclass(frozen=True)
class PlacedBlocks:
    """One replica's per-block array of a placed store, held as one array per
    chip: block b is row ``slot[b]`` of ``parts[chip[b]]``.

    Indexing reads like the (n_blocks, ...) array it stands for, on the
    leading (block) index, with further indices applied to each block.  The
    blocks read must all lie on one chip, and the result is that chip's
    array: a read across chips raises, so nothing built from a placed
    replica spans chips."""
    parts: tuple                   # per chip: (blocks held there, ...)
    chip: np.ndarray               # (n_blocks,) chip of each block
    slot: np.ndarray               # (n_blocks,) row of each block there

    @property
    def dtype(self):
        return self.parts[0].dtype

    @property
    def size(self) -> int:
        return sum(int(p.size) for p in self.parts)

    def blocks_on(self, chip: int) -> np.ndarray:
        """Block ids held on ``chip``, in slot order."""
        mine = np.flatnonzero(self.chip == chip)
        return mine[np.argsort(self.slot[mine])]

    def map_parts(self, fn) -> "PlacedBlocks":
        """A placed array laid out like this one: ``fn(part, blocks)`` makes
        each chip's part from this one's and the block ids it holds."""
        return PlacedBlocks(
            tuple(fn(p, self.blocks_on(k)) for k, p in enumerate(self.parts)),
            self.chip, self.slot)

    def __getitem__(self, key):
        blocks, rest = (key[0], key[1:]) if isinstance(key, tuple) else (key,
                                                                         ())
        if isinstance(blocks, slice):
            blocks = np.arange(len(self.chip))[blocks]
        chips = np.unique(self.chip[blocks])
        if len(chips) != 1:
            raise ValueError(f"blocks {blocks} lie on chips {chips.tolist()}: "
                             f"a read of a placed replica stays on one chip")
        return self.parts[int(chips[0])][(self.slot[blocks],) + rest]


@jax.jit
def _key_range_cols(mins, keys, n_bad):
    last = jnp.maximum(keys.shape[1] - 1 - n_bad, 0)
    return mins[:, 0], jnp.take_along_axis(keys, last[:, None], axis=1)[:, 0]


def block_key_ranges(mins, keys, n_bad) -> np.ndarray:
    """(k, 2) int64 host key ranges of k indexed blocks, from their root
    directories ``mins`` (k, parts), sorted key column ``keys`` (k, rows)
    and host bad-record counts ``n_bad`` (k,): one small program on the
    blocks' chip and one device-to-host read.  An all-bad block's row is
    meaningless (it has no good key)."""
    lo, hi = jax.device_get(_key_range_cols(mins, keys,
                                            np.asarray(n_bad, np.int32)))
    return np.stack([np.asarray(lo).astype(np.int64),
                     np.asarray(hi).astype(np.int64)], axis=1)


def replica_key_ranges(rep: "Replica", bad_counts: np.ndarray) -> np.ndarray:
    """The key ranges of every block of an indexed replica; a placed
    replica is read per chip, from that chip's part, with nothing moved
    between chips."""
    keys = rep.cols[rep.sort_key]
    if not isinstance(rep.mins, PlacedBlocks):
        return block_key_ranges(rep.mins, keys, bad_counts)
    out = np.zeros((len(rep.mins.chip), 2), np.int64)
    for k, part in enumerate(rep.mins.parts):
        blocks = rep.mins.blocks_on(k)
        if len(blocks):
            out[blocks] = block_key_ranges(part, keys.parts[k],
                                           bad_counts[blocks])
    return out


@dataclasses.dataclass
class Replica:
    """One sort order of the whole dataset: per-column (n_blocks, rows).

    ``sort_key`` is the replica's clustered-index key; ``indexed`` tracks the
    PER-BLOCK index state (adaptive uploads ship blocks unindexed and jobs
    commit indexes block by block).  An unindexed block's rows sit in upload
    order; an indexed block's rows are sorted by ``sort_key`` with bad
    records at the tail.  ``sort_key is None`` with all-False ``indexed``
    means the replica is still unclaimed — the first adaptive commit claims
    it for the workload's filter column.  In a placed store (``BlockStore.
    devices``) ``cols``, ``mins`` and ``checksums`` hold ``PlacedBlocks``.
    """
    sort_key: Optional[str]
    cols: dict[str, jax.Array]
    mins: Optional[jax.Array]              # (n_blocks, n_partitions)
    checksums: dict[str, jax.Array]        # col -> (n_blocks, n_chunks) u32
    nodes: np.ndarray                      # (n_blocks,) datanode per block
    indexed: Optional[np.ndarray] = None   # (n_blocks,) bool per-block state
    retired: bool = False                  # decommissioned TOMBSTONE: the
    #   slot stays (replica ids are baked into caches, the AccessLog and
    #   recorded plans) but planning, repair, scrubbing and byte accounting
    #   all skip it; its columns are dropped
    key_range: Optional[np.ndarray] = None  # (n_blocks, 2) int64 HOST copy
    #   of each block's root minimum ``mins[b, 0]`` and last good sorted key
    #   ``cols[sort_key][b, rows - bad[b] - 1]``, meaningful where
    #   ``indexed[b]``; set by the store's own writes (upload, commit,
    #   repair), never read back from the device, so pruning costs no sync

    def __post_init__(self):
        if self.indexed is None:
            self.indexed = np.full(len(self.nodes),
                                   self.sort_key is not None, dtype=bool)
        if self.key_range is None:
            self.key_range = np.zeros((len(self.nodes), 2), np.int64)

    def block_indexed(self, block_id: int) -> bool:
        return self.sort_key is not None and bool(self.indexed[block_id])

    @property
    def nbytes(self) -> int:
        return int(sum(v.size * v.dtype.itemsize for v in self.cols.values()))


@dataclasses.dataclass
class RepairStats:
    """What one ``repair_blocks`` pass did (feeds the repair-cost model:
    modeled repair I/O = bytes_rewritten read from the donor + written to
    the victim, over the cluster disk bandwidth)."""
    blocks_repaired: int = 0
    unrepairable: int = 0
    bytes_rewritten: int = 0
    wall_s: float = 0.0


@dataclasses.dataclass
class BlockStore:
    schema: Schema
    n_blocks: int
    rows_per_block: int
    partition_size: int
    replicas: list[Replica]
    bad_counts: np.ndarray                 # (n_blocks,) bad records per
    #   block, on the host (pruning reads it per split)
    namenode: Namenode
    layout: str = "pax"
    bad_original: Optional[jax.Array] = None  # (n_blocks, rows) upload order
    access_log: Any = None                 # governor.AccessLog (lazy, set by
    #   the record readers' note_read attribution — persistent across jobs)
    governor: Any = None                   # governor.IndexGovernor when the
    #   store is budget-governed (commit_block_indexes enforces its budget)
    block_cache: Any = None                # cache.BlockCache when a serving
    #   layer caches decoded split inputs — commit_block_indexes and
    #   demote_replica invalidate the touched replica's entries
    verify_reads: bool = True              # read-path checksum verification
    #   (amortized to BlockCache fills when a cache is attached)
    scrubber: Any = None                   # runtime.scrubber.Scrubber when
    #   background verification is attached (ticks at job/flush boundaries)
    result_cache: Any = None               # cache.ResultCache when a serving
    #   layer caches materialized answers — dropped wholesale by every
    #   destructive transition (and keyed by ``version`` as a backstop)
    replicator: Any = None                 # governor.ReplicationController
    #   when heat-driven dynamic replication is attached (ticks at
    #   job/flush boundaries like the scrubber)
    version: int = 0                       # bumped by every destructive
    #   transition; part of the result-cache key, so answers filled against
    #   an older store state are structurally unreachable
    devices: tuple = ()                    # the chips of a PLACED store:
    #   replica r's block b lives on devices[chip_of(nodes[r, b])] and its
    #   arrays are PlacedBlocks; empty: one chip holds every replica, as
    #   plain (n_blocks, ...) arrays

    @property
    def n_chips(self) -> int:
        return max(1, len(self.devices))

    def chip_of(self, node: int) -> int:
        """The chip of a datanode: nodes go round the chips, so one chip
        holds every node."""
        return int(node) % self.n_chips

    def _single_chip(self, what: str):
        if self.devices:
            raise NotImplementedError(
                f"{what} rewrites replica blocks in place, which a store "
                f"placed over {len(self.devices)} chips does not support")

    def _note_destructive(self):
        """Every state transition that changes what a query would read
        (index commit, demotion, quarantine, repair) funnels through here:
        bump the store version and drop all materialized answers."""
        self.version += 1
        if self.result_cache is not None:
            self.result_cache.invalidate_store()

    @property
    def replication(self) -> int:
        return len(self.replicas)

    def live_replica_ids(self) -> list[int]:
        """Replica slots that are not decommissioned tombstones."""
        return [i for i, r in enumerate(self.replicas) if not r.retired]

    def template_replica(self) -> Replica:
        """A live replica to read schema/dtype metadata from (replica 0
        may be a retired tombstone with its columns dropped)."""
        for r in self.replicas:
            if not r.retired:
                return r
        raise ValueError("store has no live replicas")

    def replica_for(self, key: str) -> Optional[int]:
        """Replica to READ a ``key`` index from: when several replicas share
        a sort_key (possible after demote→re-claim leaves one mid-re-key),
        prefer the one with the highest ``indexed`` fraction — it qualifies
        the most blocks for index scan; ties go to the lowest id."""
        best, best_frac = None, -1.0
        for i, r in enumerate(self.replicas):
            if not r.retired and r.sort_key == key:
                frac = float(r.indexed.mean()) if len(r.indexed) else 0.0
                if frac > best_frac:
                    best, best_frac = i, frac
        return best

    def replica_by_key(self, key: str) -> Optional[int]:
        return self.replica_for(key)

    def alive_replica_ids(self, block_id: int) -> list[int]:
        """Replica indices whose datanode for this block is alive AND whose
        copy of the block is not quarantined — the set ``plan()`` may place
        reads on."""
        out = []
        for i, r in enumerate(self.replicas):
            if r.retired:
                continue
            node = int(r.nodes[block_id])
            if (node not in self.namenode.dead
                    and not self.namenode.is_quarantined(block_id, node)):
                out.append(i)
        return out

    # -- corruption: quarantine / verification / repair ---------------------

    def quarantine_block(self, replica_id: int, block_id: int):
        """Record that this replica's copy of a block failed verification.
        The (block, node) pair leaves ``locate``/``alive_replica_ids`` (and
        hence ``plan``) until ``repair_blocks`` restores it; any cached
        gathers touching it are dropped."""
        node = int(self.replicas[replica_id].nodes[block_id])
        self.namenode.quarantine(block_id, node)
        if self.block_cache is not None:
            self.block_cache.invalidate_blocks(replica_id, [block_id])
        self._note_destructive()
        from repro.kernels import ops
        ops.DISPATCH_COUNTS["blocks_quarantined"] += 1
        from repro.obs import trace as obs_trace
        obs_trace.instant("quarantine", track="store",
                          args={"replica": replica_id, "block": block_id,
                                "node": node})

    def is_quarantined(self, replica_id: int, block_id: int) -> bool:
        return self.namenode.is_quarantined(
            block_id, int(self.replicas[replica_id].nodes[block_id]))

    def quarantined_blocks(self, replica_id: int) -> list[int]:
        nodes = self.replicas[replica_id].nodes
        return [b for b in range(self.n_blocks)
                if (b, int(nodes[b])) in self.namenode.quarantined]

    def verify_block(self, replica_id: int, block_id: int) -> bool:
        """Full integrity check of one (replica, block): every column's
        chunk checksums, plus root-directory consistency (mins re-derived
        from the verified key column) when the block is indexed.  Used by
        the scrubber and by repair-source selection."""
        from repro.kernels import ops
        rep = self.replicas[replica_id]
        names = sorted(rep.cols)
        sl = slice(block_id, block_id + 1)
        data = jnp.stack([rep.cols[c][sl] for c in names])
        sums = jnp.stack([rep.checksums[c][sl] for c in names])
        if not bool(np.asarray(ops.verify_blocks(data, sums)).all()):
            return False
        if rep.block_indexed(block_id):
            return bool(np.asarray(ops.verify_root(
                rep.mins[sl], rep.cols[rep.sort_key][sl],
                partition_size=self.partition_size)).all())
        return True

    def _healthy_source(self, victim_id: int, block_id: int) -> Optional[int]:
        """A replica that can donate this block: alive, unquarantined, and
        freshly verified (a donor with latent corruption must not launder
        its rot into the repair)."""
        for rid in self.alive_replica_ids(block_id):
            if rid != victim_id and self.verify_block(rid, block_id):
                return rid
        return None

    def repair_blocks(self) -> "RepairStats":
        """Rebuild every quarantined block of this store from a healthy
        replica — the HAIL twist being that repair PRESERVES the victim's
        clustered index instead of byte-copying the donor's (differently
        sorted) bytes:

        1. donor rows return to upload order by sorting on the logical
           ``__rowid__`` column (any replica reconstructs the logical
           block — the same invariant failover relies on);
        2. if the victim block was indexed, re-sort under the VICTIM's own
           ``sort_key`` with bad records to the tail (the stable device
           sort reproduces a fresh eager upload's layout bit-for-bit) and
           rebuild the root-directory row;
        3. splice columns + root + freshly recomputed checksums (and the
           block's host key range), clear the quarantine, and invalidate the bad-mask/block caches for just
           the touched blocks.

        The governor's AccessLog is untouched — repair restores bytes, it
        is not a workload event.  Blocks with no healthy donor stay
        quarantined and are counted ``unrepairable``.
        """
        import time as _time
        from repro.kernels import ops
        assert self.layout == "pax", "repair targets PAX replicas"
        self._single_chip("repair_blocks")
        t0 = _time.perf_counter()
        stats = RepairStats()
        by_rep: dict[int, list[int]] = {}
        node_rep = {(b, int(r.nodes[b])): i
                    for i, r in enumerate(self.replicas) if not r.retired
                    for b in range(self.n_blocks)}
        for (b, node) in sorted(self.namenode.quarantined):
            rid = node_rep.get((b, node))
            if rid is not None:
                by_rep.setdefault(rid, []).append(b)
        big = jnp.iinfo(jnp.int32).max
        for rid, blocks in sorted(by_rep.items()):
            rep = self.replicas[rid]
            repaired = []
            for b in blocks:
                src_id = self._healthy_source(rid, b)
                if src_id is None:
                    stats.unrepairable += 1
                    continue
                src = self.replicas[src_id]
                # donor -> upload order via logical row identity
                _, upload_cols, _ = ops.sort_block(
                    src.cols[ROWID][b][None],
                    {c: v[b][None] for c, v in src.cols.items()})
                if rep.block_indexed(b):
                    keys = jnp.where(self.bad_original[b][None], big,
                                     upload_cols[rep.sort_key])
                    _, new_cols, _ = ops.sort_block(keys, upload_cols)
                    roots = idx.build_block_roots(new_cols[rep.sort_key],
                                                  self.partition_size)
                    rep.mins = rep.mins.at[b].set(roots[0])
                    rep.key_range[b] = block_key_ranges(
                        roots, new_cols[rep.sort_key],
                        self.bad_counts[b:b + 1])[0]
                else:
                    new_cols = upload_cols
                    rep.mins = rep.mins.at[b].set(jnp.int32(0))
                for c, v in new_cols.items():
                    rep.cols[c] = rep.cols[c].at[b].set(v[0])
                    rep.checksums[c] = rep.checksums[c].at[b].set(
                        ck.chunk_checksums(v[0]))
                    stats.bytes_rewritten += int(
                        v[0].size * v[0].dtype.itemsize)
                self.namenode.clear_quarantine(b, int(rep.nodes[b]))
                repaired.append(b)
                stats.blocks_repaired += 1
                ops.DISPATCH_COUNTS["blocks_repaired"] += 1
            if repaired:
                self.__dict__.get("_bad_mask_cache", {}).pop(rid, None)
                if self.block_cache is not None:
                    self.block_cache.invalidate_blocks(rid, repaired)
        if stats.blocks_repaired:
            self._note_destructive()
        stats.wall_s = _time.perf_counter() - t0
        from repro.obs import trace as obs_trace
        obs_trace.complete_wall("repair_blocks", t0, stats.wall_s,
                                track="store",
                                args={"repaired": stats.blocks_repaired,
                                      "unrepairable": stats.unrepairable,
                                      "bytes": stats.bytes_rewritten})
        return stats

    @property
    def nbytes(self) -> int:
        return sum(r.nbytes for r in self.replicas)

    # -- adaptive indexing: the store is state-evolving ---------------------

    def adaptive_replica_for(self, key: str) -> Optional[int]:
        """Replica to (keep) converging toward a ``key`` index: a replica
        already keyed on ``key`` if one exists, else the first unclaimed
        (sort_key None) PAX replica.  None when every replica is claimed by
        some other key — adaptive indexing for ``key`` is then impossible."""
        rid = self.replica_by_key(key)
        if rid is not None:
            return rid
        if self.layout != "pax":
            return None
        for i, r in enumerate(self.replicas):
            if not r.retired and r.sort_key is None:
                return i
        return None

    def unindexed_blocks(self, replica_id: int) -> np.ndarray:
        return np.nonzero(~self.replicas[replica_id].indexed)[0]

    def indexed_fraction(self, key: str) -> float:
        """Fraction of blocks index-scannable for ``key`` (convergence)."""
        rid = self.replica_for(key)
        if rid is None:
            return 0.0
        return float(self.replicas[rid].indexed.mean())

    def total_indexed_blocks(self) -> int:
        """Per-block indexes held across ALL replicas — the quantity the
        governor's storage budget bounds."""
        return int(sum(int(r.indexed.sum()) for r in self.replicas
                       if r.sort_key is not None))

    def commit_block_indexes(self, replica_id: int, block_ids,
                             sort_key: str, sorted_cols: dict,
                             new_mins: jax.Array, new_checksums: dict) -> int:
        """Commit freshly built per-block clustered indexes (adaptive path).

        Splices the sorted columns, per-block root directories and recomputed
        checksums into the replica (functional ``.at`` updates — reads already
        dispatched against the old arrays are unaffected), sets the blocks'
        host key ranges from them, flips the blocks' ``indexed`` flags, advances the namenode's Dir_rep, and invalidates
        the per-replica bad-row-mask cache (tail layout changed).

        When a governor is attached, the commit is trimmed to the budget's
        remaining room (hard backstop — run_job normally demotes/trims
        BEFORE building, so a trim here means someone committed directly).
        Returns the number of blocks actually committed.
        """
        self._single_chip("commit_block_indexes")
        rep = self.replicas[replica_id]
        assert rep.sort_key in (None, sort_key), \
            f"replica {replica_id} already keyed on {rep.sort_key!r}"
        bsel = np.asarray(block_ids)
        # never commit a quarantined block: its source bytes are suspect and
        # a commit would recompute "valid" checksums over corrupt data,
        # laundering the corruption past every future verification
        clean = np.array([not self.is_quarantined(replica_id, int(b))
                          for b in bsel], dtype=bool)
        if not clean.all():
            bsel = bsel[clean]
            sorted_cols = {c: v[clean] for c, v in sorted_cols.items()}
            new_mins = new_mins[clean]
            new_checksums = {c: s[clean] for c, s in new_checksums.items()}
        if self.governor is not None:
            keep = self.governor.admit(self, replica_id, len(bsel))
            if keep < len(bsel):
                bsel = bsel[:keep]
                sorted_cols = {c: v[:keep] for c, v in sorted_cols.items()}
                new_mins = new_mins[:keep]
                new_checksums = {c: s[:keep]
                                 for c, s in new_checksums.items()}
        if len(bsel) == 0:
            return 0                     # nothing fits: do not even claim
        rep.sort_key = sort_key
        for c, v in sorted_cols.items():
            rep.cols[c] = rep.cols[c].at[bsel].set(v)
        rep.mins = idx.merge_block_roots(rep.mins, bsel, new_mins)
        rep.key_range[bsel] = block_key_ranges(
            new_mins, sorted_cols[sort_key], self.bad_counts[bsel])
        for c, s in new_checksums.items():
            rep.checksums[c] = rep.checksums[c].at[bsel].set(s)
        rep.indexed[bsel] = True
        for b in bsel:
            self.namenode.update_index(int(b), int(rep.nodes[b]), sort_key)
        self.__dict__.get("_bad_mask_cache", {}).pop(replica_id, None)
        if self.block_cache is not None:
            self.block_cache.invalidate_replica(replica_id)
        self._note_destructive()
        from repro.core import governor as gv
        gv.note_commit(self, replica_id, sort_key)
        return len(bsel)

    def demote_replica(self, replica_id: int) -> int:
        """Governor eviction: drop a replica's clustered index entirely —
        the store's first DESTRUCTIVE state transition.

        The replica's rows return to upload order by sorting on the logical
        ``__rowid__`` column (identity for blocks that were never indexed),
        the root directory and host key ranges zero, per-replica checksums are recomputed for
        the restored byte order, ``sort_key``/``indexed`` rewind to the
        unclaimed state, the namenode's Dir_rep rewinds per block, and the
        bad-row-mask cache invalidates (bad rows move from the sorted tail
        back to their original upload positions).  The replica is then
        re-claimable by a later workload via ``adaptive_replica_for`` +
        ``commit_block_indexes``.  Returns the number of per-block indexes
        dropped (budget blocks freed).
        """
        assert self.layout == "pax", "only PAX replicas carry indexes"
        self._single_chip("demote_replica")
        rep = self.replicas[replica_id]
        assert rep.sort_key is not None, \
            f"replica {replica_id} is already unindexed"
        old_key = rep.sort_key
        bsel = np.nonzero(rep.indexed)[0]       # only indexed blocks moved;
        dropped = len(bsel)                     # the rest are already in
        # quarantined blocks are NOT un-sorted or re-checksummed: their
        # bytes are corrupt, and recomputing checksums over them would
        # launder the corruption into a "verified" state.  They keep their
        # quarantine through the demotion (the budget still counts their
        # index as dropped) and are restored to upload order by
        # repair_blocks, which sees block_indexed()==False post-demote.
        qset = {int(b) for b in self.quarantined_blocks(replica_id)}
        if qset:
            bsel = np.array([b for b in bsel if int(b) not in qset],
                            dtype=np.int64)
        if len(bsel):                           # upload order (mid-re-key)
            # device-side un-sort: sorting by the logical __rowid__ column
            # IS the inverse permutation back to upload order, and it runs
            # through the same stable device sort the build path uses — so
            # the rekey_s wall charged to demotions is a device wall, not a
            # host argsort artifact.
            from repro.kernels import ops
            _, unsorted, _ = ops.sort_block(
                rep.cols[ROWID][bsel],
                {c: v[bsel] for c, v in rep.cols.items()})
            rep.cols = {c: v.at[bsel].set(unsorted[c])
                        for c, v in rep.cols.items()}
            rep.checksums = {
                c: s.at[bsel].set(jax.vmap(ck.chunk_checksums)(
                    rep.cols[c][bsel]))
                for c, s in rep.checksums.items()}
        rep.mins = jnp.zeros(
            (self.n_blocks, self.rows_per_block // self.partition_size),
            jnp.int32)
        rep.sort_key = None
        rep.indexed = np.zeros(self.n_blocks, dtype=bool)
        rep.key_range = np.zeros((self.n_blocks, 2), np.int64)
        for b in range(self.n_blocks):
            self.namenode.update_index(b, int(rep.nodes[b]), None)
        self.__dict__.get("_bad_mask_cache", {}).pop(replica_id, None)
        if self.block_cache is not None:
            self.block_cache.invalidate_replica(replica_id)
        self._note_destructive()
        if self.access_log is not None:
            self.access_log.forget_replica(replica_id)
        if self.governor is not None:
            self.governor.note_demotion(replica_id, old_key, dropped)
        return dropped

    # -- dynamic replication: replica COUNT follows measured heat -----------

    def add_replica(self, n_nodes: Optional[int] = None) -> int:
        """Scale-UP arm of dynamic replication: clone the dataset into a
        fresh, UNCLAIMED replica in upload order — claimable by the next
        adaptive job for whatever column is hot (the HAIL win: every
        replica carries its own clustered index, so adding a replica adds
        an index *slot*, not just read bandwidth).

        Per block, the first healthy (alive, unquarantined) replica
        donates; donor rows return to upload order by sorting on the
        logical ``__rowid__`` column (the same device-side un-sort repair
        and demotion use — identity for unindexed donors), and checksums
        are recomputed for the restored byte order.  Placement stays
        consistent with ``assign_nodes``: block b lands on
        ``(b + slot) % n_nodes`` for the lowest node-offset ``slot`` no
        live replica occupies, preserving the distinct-nodes invariant.

        Appending is NON-destructive — planning prefers the lowest alive
        id for full scans and the new replica is unindexed, so no existing
        plan, cached gather or materialized answer changes meaning; the
        store version is untouched.  Returns the new replica id.
        """
        from repro.kernels import ops
        assert self.layout == "pax", "dynamic replication targets PAX stores"
        self._single_chip("add_replica")
        live = self.live_replica_ids()
        if n_nodes is None:
            n_nodes = max(int(self.replicas[i].nodes.max())
                          for i in live) + 1
        taken = {int(self.replicas[i].nodes[0]) % n_nodes for i in live}
        free = [s for s in range(n_nodes) if s not in taken]
        if not free:
            raise ValueError(
                f"cannot add replica: all {n_nodes} node offsets hold a "
                f"live replica (replication would exceed cluster size)")
        slot = free[0]
        donor = np.empty(self.n_blocks, dtype=np.int64)
        for b in range(self.n_blocks):
            alive = self.alive_replica_ids(b)
            if not alive:
                raise ValueError(
                    f"cannot add replica: block {b} has no healthy copy "
                    f"to clone from")
            donor[b] = alive[0]
        tmpl = self.template_replica()
        rows = self.rows_per_block
        new_cols = {c: jnp.zeros((self.n_blocks, rows), v.dtype)
                    for c, v in tmpl.cols.items()}
        for rid in np.unique(donor):
            bsel = np.nonzero(donor == rid)[0]
            src = self.replicas[int(rid)]
            # donor -> upload order via logical row identity (one batched
            # device sort per donor replica, not one per block)
            _, up, _ = ops.sort_block(
                src.cols[ROWID][bsel],
                {c: v[bsel] for c, v in src.cols.items()})
            new_cols = {c: new_cols[c].at[bsel].set(up[c])
                        for c in new_cols}
        new_sums = {c: jax.vmap(ck.chunk_checksums)(v)
                    for c, v in new_cols.items()}
        nodes = np.array([(b % n_nodes + slot) % n_nodes
                          for b in range(self.n_blocks)], dtype=np.int64)
        rep = Replica(sort_key=None, cols=new_cols,
                      mins=jnp.zeros(
                          (self.n_blocks, rows // self.partition_size),
                          jnp.int32),
                      checksums=new_sums, nodes=nodes)
        self.replicas.append(rep)
        rid = len(self.replicas) - 1
        per_block_bytes = rep.nbytes // self.n_blocks
        for b in range(self.n_blocks):
            self.namenode.register(ReplicaInfo(
                block_id=b, node=int(nodes[b]), sort_key=None,
                partition_size=self.partition_size, n_rows=rows,
                layout="pax", nbytes=per_block_bytes))
        ops.DISPATCH_COUNTS["replicas_added"] += 1
        from repro.obs import trace as obs_trace
        obs_trace.instant("add_replica", track="store",
                          args={"replica": rid, "node_offset": slot})
        return rid

    def decommission_replica(self, replica_id: int) -> int:
        """Scale-DOWN arm of dynamic replication: retire a cold replica —
        a DESTRUCTIVE transition like demotion, but terminal.

        The replica becomes a tombstone: its slot stays (replica ids are
        baked into caches, the AccessLog and recorded plans — removal
        would silently re-key every later replica) but ``retired`` drops
        it from planning, repair, scrubbing and byte accounting, its
        columns/checksums are freed, and the namenode unregisters every
        (block, node) pair — including quarantined ones, so a replica
        rotting in quarantine can still be decommissioned.  Bumps
        ``store.version`` and invalidates both cache tiers.

        Refuses (typed ``ValueError``) when any block would lose its last
        healthy copy.  Returns the number of per-block indexes dropped.
        """
        assert self.layout == "pax", "dynamic replication targets PAX stores"
        rep = self.replicas[replica_id]
        if rep.retired:
            raise ValueError(f"replica {replica_id} is already retired")
        for b in range(self.n_blocks):
            others = [i for i in self.alive_replica_ids(b)
                      if i != replica_id]
            if not others:
                raise ValueError(
                    f"cannot decommission replica {replica_id}: block {b} "
                    f"would lose its last healthy copy")
        dropped = (int(rep.indexed.sum())
                   if rep.sort_key is not None else 0)
        for b in range(self.n_blocks):
            self.namenode.unregister(b, int(rep.nodes[b]))
        rep.retired = True
        rep.sort_key = None
        rep.indexed = np.zeros(self.n_blocks, dtype=bool)
        rep.cols = {}
        rep.checksums = {}
        rep.mins = None
        rep.key_range = None
        self.__dict__.get("_bad_mask_cache", {}).pop(replica_id, None)
        if self.block_cache is not None:
            self.block_cache.invalidate_replica(replica_id)
        self._note_destructive()
        if self.access_log is not None:
            self.access_log.forget_replica(replica_id)
        from repro.kernels import ops
        ops.DISPATCH_COUNTS["replicas_decommissioned"] += 1
        from repro.obs import trace as obs_trace
        obs_trace.instant("decommission_replica", track="store",
                          args={"replica": replica_id,
                                "indexes_dropped": dropped})
        return dropped


def assign_nodes(n_blocks: int, replication: int, n_nodes: int) -> np.ndarray:
    """(replication, n_blocks) datanode placement: replicas of a block land
    on distinct nodes (HDFS invariant), blocks round-robin."""
    if replication > n_nodes:
        raise ValueError(
            f"replication={replication} exceeds cluster size "
            f"n_nodes={n_nodes}: replicas of a block must land on "
            f"distinct nodes")
    out = np.zeros((replication, n_blocks), dtype=np.int64)
    for b in range(n_blocks):
        base = b % n_nodes
        for r in range(replication):
            out[r, b] = (base + r) % n_nodes
    return out
