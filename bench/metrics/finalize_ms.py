"""finalize_ms: mean wall of the program's ``finalize`` span (one per
answered query: the device->host copy of its masks and projection, host
masking and concatenation), in ms, from the obs tracer."""
from bench.metrics.dispatch_ms import mean_ms


def read(rec):
    return mean_ms(rec, "finalize")
