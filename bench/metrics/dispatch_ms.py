"""dispatch_ms: mean wall of the program's ``dispatch`` span, in ms, from
the obs tracer of the traced run.  A batch opens one per split it
examines: pruning (``prune``), the gather and block cache (``gather``) and
the fused reader's call with its eager result slicing (``issue``).

``spans`` pairs an obs span's B and E events; the other readers of obs
spans use it too."""


def spans(rec, name):
    """(start_us, end_us, args) of every closed ``name`` span among
    ``rec["obs_events"]``, in order of start."""
    open_, out = {}, []
    for ev in rec.get("obs_events") or ():
        if ev.get("name") != name:
            continue
        if ev["ph"] == "B":
            open_.setdefault(ev["tid"], []).append(ev)
        elif ev["ph"] == "E" and open_.get(ev["tid"]):
            b = open_[ev["tid"]].pop()
            out.append((b["ts"], ev["ts"], b.get("args") or {}))
    return sorted(out, key=lambda s: s[0])


def mean_ms(rec, name):
    durs = [e - s for s, e, _ in spans(rec, name)]
    return sum(durs) / len(durs) / 1e3 if durs else None


def read(rec):
    return mean_ms(rec, "dispatch")
