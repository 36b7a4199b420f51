"""plan_ms: mean wall of the program's ``plan`` span (``core/query.plan``,
one per shared-scan batch), in ms, from the obs tracer of the traced run."""


def read(rec):
    starts, durs = {}, []
    for ev in rec.get("obs_events") or ():
        if ev.get("name") != "plan":
            continue
        if ev["ph"] == "B":
            starts.setdefault(ev["tid"], []).append(ev["ts"])
        elif ev["ph"] == "E" and starts.get(ev["tid"]):
            durs.append(ev["ts"] - starts[ev["tid"]].pop())
    return sum(durs) / len(durs) / 1e3 if durs else None
