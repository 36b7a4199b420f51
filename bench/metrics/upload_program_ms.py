"""upload_program_ms: device time of the jitted HAIL upload program
(parse, three sorts, root directories, checksums) per upload call, in ms,
from the traced window."""

PROGRAM = "_hail_block"


def read(rec):
    trace, n = rec.get("trace"), len(rec.get("uploads") or ())
    if trace is None or not n:
        return None
    s = trace.program_s(PROGRAM)
    return None if s is None else s / n * 1e3
