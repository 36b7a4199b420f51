"""chip_balance_pct: how evenly a flush's blocks go over the chips, in %:
per flush, the blocks of the splits dispatched on each chip (the live
``dispatch`` spans' ``chip`` and ``blocks``), their mean over the chips over
their largest, x 100; the mean over the window's flushes.  The chips are
those that any ``dispatch`` of the window names.  A program whose
``dispatch`` spans carry no ``chip`` gives no number."""
import bisect

from bench.metrics.dispatch_ms import spans


def read(rec):
    disp = [(s, a) for s, _, a in spans(rec, "dispatch") if "chip" in a]
    chips = {a["chip"] for _, a in disp}
    flushes = spans(rec, "flush")
    starts = [s for s, _, _ in flushes]
    per_flush: dict = {}
    for s, a in disp:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or flushes[i][1] < s or not a.get("live"):
            continue                       # outside a flush, or not issued
        blocks = per_flush.setdefault(i, dict.fromkeys(chips, 0))
        blocks[a["chip"]] += len(a.get("blocks") or ())
    shares = [100.0 * sum(b.values()) / len(b) / max(b.values())
              for b in per_flush.values() if max(b.values())]
    return sum(shares) / len(shares) if shares else None
