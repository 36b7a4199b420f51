"""batch_wait_ms: per query of a shared-scan batch, the time from the start
of its flush to the start of its batch (the batches before it, planning
and batching), averaged over the queries, in ms, from the obs tracer's
``flush`` and ``batch`` spans."""
import bisect

from bench.metrics.dispatch_ms import spans


def read(rec):
    flushes = spans(rec, "flush")
    starts = [s for s, _, _ in flushes]
    total = n = 0
    for s, _, args in spans(rec, "batch"):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or flushes[i][1] < s:
            continue                       # no flush encloses this batch
        width = len(args.get("tickets") or ())
        total += (s - starts[i]) * width
        n += width
    return total / n / 1e3 if n else None
