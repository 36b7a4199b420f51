"""Peaks of the chips the benchmark runs on, and the least bytes a flush of
range queries has to move: the HBM-bandwidth roofline of a scan.

The least bytes are counted from the queries and the generated columns
alone, never from how the reader or the batching works, so that no
implementation can go under them:

* for each filter column, 4 B of key for every row that qualifies for at
  least one of the flush's queries on it (every row of the table where no
  replica is clustered on the column);
* for each projected column, the row id included, 4 B read and 4 B
  written for every row that qualifies for at least one query projecting
  it;
* for each query, its result: the cheaper of 4 B per qualifying row and a
  bitmap over the rows its filter column's queries qualify.
"""
from __future__ import annotations

import numpy as np

ROWID = "__rowid__"

# Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s,
# 197 TFLOP/s bf16.  Keyed by jax's ``device_kind``.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       f"published numbers to bench/roofline.py") from None


def least_bytes(queries, cols: dict, bad: np.ndarray,
                clustered: set) -> int:
    """Least HBM bytes to answer ``queries`` (one flush) over the table
    whose columns are ``cols``; ``clustered`` names the filter columns
    some replica is clustered on."""
    n = len(bad)
    masks = [(cols[q.column] >= q.lo) & (cols[q.column] <= q.hi) & ~bad
             for q in queries]
    total = 0
    by_filter: dict = {}
    for q, m in zip(queries, masks):
        by_filter.setdefault(q.column, []).append(m)
    union_rows = {}
    for col, ms in by_filter.items():
        u = np.logical_or.reduce(ms)
        union_rows[col] = int(np.count_nonzero(u))
        total += 4 * (union_rows[col] if col in clustered else n)
    projected: dict = {}
    for q, m in zip(queries, masks):
        for c in tuple(q.projection) + (ROWID,):
            projected.setdefault(c, []).append(m)
    for c, ms in projected.items():
        total += 8 * int(np.count_nonzero(np.logical_or.reduce(ms)))
    for q, m in zip(queries, masks):
        k = int(np.count_nonzero(m))
        total += min(4 * k, -(-union_rows[q.column] // 8))
    return total
