"""Record the small profiler trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>     # on a TPU

Inside a ``bench:window`` annotation: one HAIL upload of two 4,096-row
UserVisits blocks (``bench:hail_upload``) and three fused-reader flushes
of two queries each (``bench:flush``), with host sleeps between them so
the trace has idle gaps under known annotations.
"""
from __future__ import annotations

import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    from bench import datagen
    from bench.harness import (load_benchmark, profile_options,
                               program_schema, resolve)
    from repro.core import query as hq
    from repro.core import upload as up

    cfg = dict(resolve(load_benchmark(), "uservisits.bob").config,
               n_blocks=2, rows_per_block=4096)
    text, cols, bad = datagen.make_table(cfg, 3)
    schema = program_schema(cfg)
    store, _ = up.hail_upload(schema, text, index_columns=cfg["replicas"],
                              partition_size=128)
    queries = [hq.HailQuery(("visitDate", 8000 + 100 * i, 8155 + 100 * i),
                            ("sourceIP",)) for i in range(2)]
    qplan = hq.plan(store, queries[0])
    res, _ = hq.read_hail_batch(store, queries, qplan)
    jax.block_until_ready(res[0].mask)
    (ROOT / ".bench_out").mkdir(exist_ok=True)
    tmp = tempfile.mkdtemp(dir=ROOT / ".bench_out")
    jax.profiler.start_trace(tmp, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:hail_upload"):
            store, _ = up.hail_upload(schema, text,
                                      index_columns=cfg["replicas"],
                                      partition_size=128)
        time.sleep(0.005)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:flush"):
                res, _ = hq.read_hail_batch(store, queries, qplan)
                jax.block_until_ready(res[0].mask)
                time.sleep(0.002)
    jax.profiler.stop_trace()
    found = sorted(pathlib.Path(tmp).glob("**/*.xplane.pb"))
    shutil.copy(found[-1], out)
    shutil.rmtree(tmp)
    print(f"wrote {out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
