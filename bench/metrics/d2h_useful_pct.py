"""d2h_useful_pct: bytes of the answers over bytes copied from the device
to the host, in %, summed over the window's ``finalize`` spans
(``answer_bytes`` / ``d2h_bytes``, counted by the program)."""
from bench.metrics.dispatch_ms import spans


def read(rec):
    fin = [a for _, _, a in spans(rec, "finalize")]
    d2h = sum(a.get("d2h_bytes", 0) for a in fin)
    if not d2h:
        return None
    return 100.0 * sum(a.get("answer_bytes", 0) for a in fin) / d2h
