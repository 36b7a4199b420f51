"""hail_reader_roofline: the least bytes the window's flushes had to move
(``bench/roofline.least_bytes``) over the chip's HBM bandwidth, as a share
of the device time of the fused reader's jitted program (root lookup and
``pallas_call``) in the traced window."""
from bench import roofline

PROGRAM = "hail_read_batch"


def read(rec):
    trace, least = rec.get("trace"), rec.get("least_bytes")
    if trace is None or not least:
        return None
    reader_s = trace.program_s(PROGRAM)
    if not reader_s:
        return None
    bw = roofline.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / bw) / reader_s
