"""The least-bytes count of the reader's roofline, against a count row by
row, and the peaks table."""
from __future__ import annotations

import numpy as np
import pytest

from bench import roofline
from bench.querygen import Query


def brute_force(queries, cols, bad, clustered):
    n = len(bad)
    total = 0
    qual = [[cols[q.column][r] >= q.lo and cols[q.column][r] <= q.hi
             and not bad[r] for r in range(n)] for q in queries]
    filters = {q.column for q in queries}
    union_rows = {}
    for f in filters:
        rows = [r for r in range(n)
                if any(m[r] for q, m in zip(queries, qual) if q.column == f)]
        union_rows[f] = len(rows)
        total += 4 * (len(rows) if f in clustered else n)
    projected = {c for q in queries for c in q.projection + ("__rowid__",)}
    for c in projected:
        total += 8 * sum(
            1 for r in range(n)
            if any(m[r] for q, m in zip(queries, qual)
                   if c in q.projection + ("__rowid__",)))
    for q, m in zip(queries, qual):
        total += min(4 * sum(m), -(-union_rows[q.column] // 8))
    return total


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_least_bytes_matches_a_row_by_row_count(seed):
    r = np.random.default_rng(seed)
    n = 300
    cols = {"a": r.integers(0, 50, n), "b": r.integers(0, 50, n),
            "c": r.integers(0, 50, n)}
    bad = r.random(n) < 0.05
    queries = [Query("q1", "a", 10, 20, ("b",)),
               Query("q2", "a", 15, 40, ("b", "c")),
               Query("q3", "b", 7, 7, ("c",)),
               Query("q4", "c", 0, 49, ("a",))]
    clustered = {"a", "b"}
    assert roofline.least_bytes(queries, cols, bad, clustered) == \
        brute_force(queries, cols, bad, clustered)


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
