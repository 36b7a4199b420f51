"""The plain reference: the same semantics as the program, in numpy, from
the generated columns and the generator's bad-row set.  Nothing of the
program is imported or used.

* A range query's answer is every good row whose filter value lies in
  ``[lo, hi]``: its row id (upload position) and its projected values.
* An upload turns each block of text into one replica per sort key: the
  parsed columns (bad rows zeroed) plus the row id, stably sorted on the
  key with bad rows at the tail, the root directory (the key at the start
  of every partition), and per-column chunk checksums over the replica's
  own order (HDFS's 512-byte chunks, position-weighted Fletcher sums).

Each ``control_*`` function is this reference with one stated guarantee
broken; the benchmark's comparison has to find it wrong.
"""
from __future__ import annotations

import numpy as np

ROWID = "__rowid__"
CHUNK = 512
_P = 65521
INT32_MAX = np.iinfo(np.int32).max


# -- queries -----------------------------------------------------------------


def answer(cols: dict, bad: np.ndarray, query) -> np.ndarray:
    """Row ids of the query's answer, ascending."""
    key = cols[query.column]
    return np.flatnonzero((key >= query.lo) & (key <= query.hi) & ~bad)


def compare_answer(cols: dict, want: np.ndarray, got_rows: dict,
                   projection) -> tuple[int, int]:
    """-> (rows missing or extra, projected values that differ) of one
    answer against the reference row ids ``want``."""
    got = np.asarray(got_rows[ROWID])
    order = np.argsort(got, kind="stable")
    got_sorted = got[order]
    if np.array_equal(got_sorted, want):
        common, i_got = want, np.arange(len(want))
    else:
        common, _, i_got = np.intersect1d(want, got_sorted,
                                          return_indices=True)
    wrong_rows = len(want) + len(got_sorted) - 2 * len(common)
    wrong_values = 0
    for c in projection:
        vals = np.asarray(got_rows[c])[order][i_got]
        wrong_values += int(np.count_nonzero(vals != cols[c][common]))
    return int(wrong_rows), wrong_values


def partition_index(cols: dict, bad: np.ndarray, column: str,
                    rows_per_block: int, partition_size: int) -> list:
    """Per block: (row order sorted on ``column`` with bad rows last,
    partition minima) -- what a replica clustered on ``column`` holds."""
    key_all = cols[column]
    out = []
    for b in range(len(key_all) // rows_per_block):
        sl = slice(b * rows_per_block, (b + 1) * rows_per_block)
        key = np.where(bad[sl], INT32_MAX, key_all[sl])
        order = np.argsort(key, kind="stable")
        out.append((order, key[order][::partition_size]))
    return out


def control_answer(bad: np.ndarray, query, index: list,
                   rows_per_block: int, partition_size: int) -> np.ndarray:
    """The reference without the post-filter: every good row of each index
    partition the range touches (the rows an index scan reads), so the
    guarantee of an exact row set is broken.  ``index`` is
    ``partition_index`` of the query's filter column."""
    out = []
    for b, (order, mins) in enumerate(index):
        first = max(int(np.searchsorted(mins, query.lo, "left")) - 1, 0)
        last = max(int(np.searchsorted(mins, query.hi, "right")) - 1, 0)
        rows = order[first * partition_size:(last + 1) * partition_size]
        rows = rows + b * rows_per_block
        out.append(rows[~bad[rows]])
    return np.sort(np.concatenate(out))


# -- upload ------------------------------------------------------------------


def checksums(col: np.ndarray) -> np.ndarray:
    """(B, R) int32 -> (B, chunks) uint32 position-weighted chunk sums over
    each block's little-endian bytes."""
    b = col.shape[0]
    raw = np.ascontiguousarray(col.astype("<i4")).view(np.uint8)
    raw = raw.reshape(b, -1)
    pad = (-raw.shape[1]) % CHUNK
    if pad:
        raw = np.pad(raw, ((0, 0), (0, pad)))
    chunks = raw.reshape(b, -1, CHUNK).astype(np.uint32)
    weights = (np.arange(CHUNK, dtype=np.uint32) % _P) + 1
    s1 = chunks.sum(axis=2, dtype=np.uint32) % _P
    s2 = (chunks * weights).sum(axis=2, dtype=np.uint32) % _P
    return (s2 << 16) | s1


def parsed_block_columns(cols: dict, bad: np.ndarray, names, first_row: int,
                         n_blocks: int, rows: int) -> dict:
    """Columns of ``n_blocks`` blocks starting at row ``first_row`` as an
    upload parses them: (B, R) each, bad rows zeroed, plus the row id
    (block number within the upload times ``rows`` plus the position)."""
    sl = slice(first_row, first_row + n_blocks * rows)
    b = bad[sl].reshape(n_blocks, rows)
    out = {c: np.where(b, 0, cols[c][sl].reshape(n_blocks, rows))
           for c in names}
    out[ROWID] = np.arange(n_blocks * rows, dtype=np.int32).reshape(
        n_blocks, rows)
    return out


def replica(parsed: dict, bad: np.ndarray, key: str,
            partition_size: int) -> dict:
    """One replica of the uploaded blocks: {"cols", "mins", "checksums"}."""
    k = np.where(bad, INT32_MAX, parsed[key])
    perm = np.argsort(k, axis=1, kind="stable")
    cols = {c: np.take_along_axis(v, perm, axis=1) for c, v in parsed.items()}
    return {"cols": cols,
            "mins": cols[key][:, ::partition_size],
            "checksums": {c: checksums(v) for c, v in cols.items()}}


def control_replica(parsed: dict, bad: np.ndarray, key: str,
                    partition_size: int) -> dict:
    """The reference replica with its checksums taken over the upload order
    (computed once, as HDFS does, and not recomputed per replica), so the
    guarantee of checksums over each replica's own order is broken."""
    rep = replica(parsed, bad, key, partition_size)
    rep["checksums"] = {c: checksums(v) for c, v in parsed.items()}
    return rep


def order_breaks(sorted_key: np.ndarray, n_bad: np.ndarray) -> int:
    """Adjacent good rows out of key order, over (B, R) blocks whose last
    ``n_bad[b]`` rows are the bad ones."""
    breaks = 0
    for b in range(sorted_key.shape[0]):
        good = sorted_key[b, :sorted_key.shape[1] - int(n_bad[b])]
        breaks += int(np.count_nonzero(good[1:] < good[:-1]))
    return breaks


def compare_replica(got: dict, want: dict, n_bad: np.ndarray,
                    key: str, got_bad_counts: np.ndarray) -> dict:
    """Counts of what differs between one uploaded replica (host arrays of
    the same layout as ``replica``'s) and the reference's."""
    out = {"row_mismatch": 0, "order_breaks": 0, "root_mismatch": 0,
           "checksum_mismatch": 0,
           "bad_count_mismatch": int(np.count_nonzero(
               np.asarray(got_bad_counts) != n_bad))}
    if "cols" in got:
        for c, v in want["cols"].items():
            g = got["cols"].get(c)
            out["row_mismatch"] += (v.size if g is None else
                                    int(np.count_nonzero(np.asarray(g) != v)))
        out["order_breaks"] = order_breaks(np.asarray(got["cols"][key]),
                                           n_bad)
    out["root_mismatch"] = int(np.count_nonzero(
        np.asarray(got["mins"]) != want["mins"]))
    for c, v in want["checksums"].items():
        g = got["checksums"].get(c)
        out["checksum_mismatch"] += (v.size if g is None else
                                     int(np.count_nonzero(np.asarray(g) != v)))
    return out
