"""Sparse clustered index (paper §3.5, Figure 2).

After sorting a block by the index key, the index is a single root directory
of partition-minimum keys over fixed 1,024-row partitions; leaves (the
partitions) are contiguous on disk/HBM so child offsets are implicit
(leaf_id * partition_size).  A range lookup binary-searches the root in main
memory for the first and last qualifying partition, streams exactly those
partitions, and post-filters — the paper's argument for why a single-level
sparse tree beats multi-level trees at <=1GB blocks (seek-dominated) maps to
one VMEM-resident root array per block here.

The Pallas kernels in repro/kernels mirror these reference semantics
(index_search, pax_scan); this module is the pure-jnp oracle.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

PARTITION = 1024  # rows per leaf partition (paper's default)


@dataclasses.dataclass(frozen=True)
class ClusteredIndex:
    """Root directory for one block: mins (n_parts,), key column name."""
    key: str
    partition_size: int


def sort_permutation(key_col: jax.Array, bad: jax.Array | None = None) -> jax.Array:
    """Permutation sorting the block by key; bad records go to the tail
    (the paper's 'special part of the data block').  Keys are int32 with
    INT32_MAX reserved as the bad-record sentinel (schema contract)."""
    k = key_col
    if bad is not None:
        big = jnp.iinfo(jnp.int32).max
        k = jnp.where(bad, big, k)
    return jnp.argsort(k, stable=True)


def build_root(sorted_key: jax.Array, partition_size: int = PARTITION) -> jax.Array:
    """Partition minima (the root directory). rows % partition_size == 0."""
    return sorted_key[::partition_size]


def build_block_roots(sorted_keys: jax.Array,
                      partition_size: int = PARTITION) -> jax.Array:
    """Batched ``build_root``: (k_blocks, rows) -> (k_blocks, n_parts)."""
    return sorted_keys[:, ::partition_size]


def merge_block_roots(mins: jax.Array, block_ids,
                      new_mins: jax.Array) -> jax.Array:
    """Incremental root-directory merge (adaptive indexing): splice freshly
    built per-block root directories into a replica's (n_blocks, n_parts)
    directory.  Functional — readers holding the old directory are
    unaffected; the store swaps in the merged one at commit."""
    return mins.at[jnp.asarray(block_ids)].set(new_mins)


def search_range(mins: jax.Array, lo, hi, partition_size: int,
                 n_rows: int) -> tuple[jax.Array, jax.Array]:
    """-> (row_start, row_end) half-open row range covering [lo, hi].

    p_first = last partition whose min < lo (clamped to 0) — a key equal
              to lo may end the partition before the first whose min is lo;
    p_last  = last partition whose min <= hi.
    """
    p_first = jnp.maximum(
        jnp.searchsorted(mins, lo, side="left").astype(jnp.int32) - 1, 0)
    p_last = jnp.maximum(
        jnp.searchsorted(mins, hi, side="right").astype(jnp.int32) - 1, 0)
    row_start = p_first * partition_size
    row_end = jnp.minimum((p_last + 1) * partition_size, n_rows)
    return row_start, row_end


def index_scan_mask(sorted_key: jax.Array, mins: jax.Array, lo, hi,
                    partition_size: int = PARTITION) -> jax.Array:
    """Qualifying-row mask touching only rows inside the partition range.

    (In the fixed-shape jnp oracle the mask is full-length; the *read set*
    is row_start:row_end — kernels and cost accounting use that.)
    """
    n = sorted_key.shape[0]
    row_start, row_end = search_range(mins, lo, hi, partition_size, n)
    r = jnp.arange(n, dtype=jnp.int32)
    in_range = (r >= row_start) & (r < row_end)
    pred = (sorted_key >= lo) & (sorted_key <= hi)
    return in_range & pred


def full_scan_mask(key_col: jax.Array, lo, hi) -> jax.Array:
    return (key_col >= lo) & (key_col <= hi)


def rows_read_fraction(mins: jax.Array, lo, hi, partition_size: int,
                       n_rows: int) -> jax.Array:
    """Fraction of the block the index scan must read (I/O model)."""
    row_start, row_end = search_range(mins, lo, hi, partition_size, n_rows)
    return (row_end - row_start) / n_rows
