"""chips_in_flight: at the start of each ``issue`` span, the number of
chips that hold a split issued and not yet waited on, the split being
issued counted; the mean over the window's issues.  A split is waited on
once its ``wait`` span ends; the ``issue`` and ``wait`` spans carry the
split's ``chip``.  A program whose ``issue`` spans carry no ``chip`` gives
no number."""
import collections

from bench.metrics.dispatch_ms import spans


def read(rec):
    events = [(s, 1, a["chip"]) for s, _, a in spans(rec, "issue")
              if "chip" in a]
    if not events:
        return None
    events += [(e, 0, a["chip"]) for _, e, a in spans(rec, "wait")
               if "chip" in a]
    open_ = collections.Counter()
    counts = []
    for _, is_issue, chip in sorted(events, key=lambda ev: ev[:2]):
        if is_issue:
            open_[chip] += 1
            counts.append(sum(1 for n in open_.values() if n > 0))
        elif open_[chip] > 0:
            open_[chip] -= 1
    return sum(counts) / len(counts)
