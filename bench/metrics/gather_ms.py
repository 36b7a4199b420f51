"""gather_ms: mean wall of the program's ``gather`` span (one per split a
shared scan reads: block-cache lookup, and on a miss the checksum verify
and the device gather of ``cache_fill``), in ms, from the obs tracer."""
from bench.metrics.dispatch_ms import mean_ms


def read(rec):
    return mean_ms(rec, "gather")
