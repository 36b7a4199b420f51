"""The yardstick's own arithmetic: percentiles and the compile counter.

Copied from the program (``obs/metrics.nearest_rank`` and the compile
counter of ``chip_smoke.py``) so that a later change to the program cannot
change how the benchmark counts.
"""
from __future__ import annotations

import collections
import math


def nearest_rank(values, p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``p``% of
    the sample at or below it, never interpolated.

    >>> nearest_rank([10.0, 20.0, 30.0, 40.0], 50)
    20.0
    >>> nearest_rank([40.0, 10.0, 30.0, 20.0], 90)
    40.0
    """
    vals = sorted(values)
    if not vals:
        raise ValueError("nearest_rank of an empty sample")
    k = max(1, math.ceil(float(p) / 100.0 * len(vals)))
    return float(vals[min(k, len(vals)) - 1])


class CompileCounter:
    """XLA compiles, their seconds and persistent-cache hits and misses,
    from JAX's monitoring events.  JAX offers no way to remove a listener,
    so one counter is registered per process (``compile_counter``)."""

    def __init__(self):
        import jax
        self.counts: collections.Counter = collections.Counter()

        def on_event(event, **_):
            if event.startswith("/jax/compilation_cache/cache_"):
                self.counts[event.rsplit("/", 1)[1]] += 1

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.counts["compiles"] += 1
                self.counts["compile_s"] += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> collections.Counter:
        return collections.Counter(self.counts)


_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER
