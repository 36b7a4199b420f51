"""Repeated HAIL uploads of text from host memory.

Set-up makes the configuration's table on the device from the seed, copies
its text to the host as ``n_blocks / chunk_blocks`` distinct chunks, and
warms the upload program with one upload of the first chunk.  The window
uploads the chunks in turn through the program's ``hail_upload`` into the
configuration's indexed, checksummed replicas, each call timed from host
bytes to ``block_until_ready``; the last ``resident`` stores stay on the
device and the oldest is freed.  Uploads start until ``seconds`` have
passed; the window ends when the last one returns.

After the window every upload's root directories, bad-row counts and
checksums, and every column of every replica of the resident stores, are
compared with the reference.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time

import numpy as np

from bench import datagen, reference


@dataclasses.dataclass
class State:
    cfg: dict
    schema: object
    chunks: list                   # host text, (chunk_blocks, R, W) each
    cols: dict
    bad: np.ndarray
    chunk_blocks: int
    resident: int
    log: object


def _upload(state: State, text):
    from repro.core import upload as up
    return up.hail_upload(state.schema, text,
                          index_columns=state.cfg["replicas"],
                          partition_size=state.cfg["partition_size"])


def setup(ctx) -> State:
    from bench.harness import program_schema

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    cb = int(traffic["chunk_blocks"])
    if cfg["n_blocks"] % cb:
        raise ValueError(f"{cfg['n_blocks']} blocks do not split into "
                         f"chunks of {cb}")
    t = time.perf_counter()
    text, cols, bad = datagen.make_table(cfg, ctx.seed)
    chunks = [np.asarray(text[i:i + cb])
              for i in range(0, cfg["n_blocks"], cb)]
    del text
    ctx.log(f"data: {len(chunks)} chunks of {chunks[0].nbytes} bytes of "
            f"text made on the device and copied to the host in "
            f"{time.perf_counter() - t:.3f} s, {int(bad.sum())} bad rows")
    state = State(cfg, program_schema(cfg), chunks, cols, bad, cb,
                  int(traffic["resident"]), ctx.log)
    t = time.perf_counter()
    store, _ = _upload(state, chunks[0])
    del store
    gc.collect()
    ctx.log(f"warm-up upload: {time.perf_counter() - t:.3f} s")
    return state


def window(state: State, seconds: float) -> dict:
    import jax

    resident = collections.deque(maxlen=state.resident)
    uploads = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        i = len(uploads) % len(state.chunks)
        with jax.profiler.TraceAnnotation("bench:hail_upload"):
            t = time.perf_counter()
            store, st = _upload(state, state.chunks[i])
            dt = time.perf_counter() - t
        uploads.append({"chunk": i, "s": dt, "ascii_bytes": st.ascii_bytes,
                        "replicas": [{"mins": r.mins,
                                      "checksums": dict(r.checksums)}
                                     for r in store.replicas],
                        "bad_counts": store.bad_counts})
        resident.append((i, store))
    window_s = time.perf_counter() - t0
    total = sum(u["ascii_bytes"] for u in uploads)
    times = [u["s"] for u in uploads]
    slowest = max(range(len(times)), key=times.__getitem__)
    state.log(f"uploads: {len(uploads)} in {window_s:.3f} s, {total} bytes "
              f"of text; median {float(np.median(times)):.3f} s, slowest "
              f"{times[slowest]:.3f} s (upload {slowest})")
    return {"window_s": window_s, "attempted": len(uploads), "failed": 0,
            "e2e": {"upload_mb_s": total / window_s / 1e6},
            "uploads": uploads, "resident": list(resident)}


def references(state: State, make=reference.replica) -> list:
    """Per chunk: (reference replicas, bad-row counts per block)."""
    rows = state.cfg["rows_per_block"]
    names = [c["name"] for c in state.cfg["columns"]]
    out = []
    for i in range(len(state.chunks)):
        first = i * state.chunk_blocks * rows
        parsed = reference.parsed_block_columns(
            state.cols, state.bad, names, first, state.chunk_blocks, rows)
        bad = state.bad[first:first + state.chunk_blocks * rows].reshape(
            state.chunk_blocks, rows)
        reps = [make(parsed, bad, key, state.cfg["partition_size"])
                for key in state.cfg["replicas"]]
        out.append((reps, bad.sum(axis=1)))
    return out


def compare(state: State, uploads, refs) -> tuple[dict, int]:
    """Mismatch counts over uploads [(chunk, [replica dicts], bad_counts)],
    each replica dict holding host ``mins``, ``checksums`` and, where the
    store is still resident, ``cols``; and the count of wrong uploads."""
    total = collections.Counter()
    n_wrong = 0
    for chunk, reps, bad_counts in uploads:
        want, n_bad = refs[chunk]
        wrong = False
        for got, w, key in zip(reps, want, state.cfg["replicas"]):
            c = reference.compare_replica(got, w, n_bad, key, bad_counts)
            total.update(c)
            wrong |= any(c.values())
        n_wrong += wrong
    return dict(total), n_wrong


def check(state: State, rec: dict) -> tuple[dict, int]:
    t = time.perf_counter()
    refs = references(state)
    state.log(f"reference replicas: {time.perf_counter() - t:.3f} s")
    stores = rec.pop("resident")
    resident = {id(u): s for (_, s), u in
                zip(stores, rec["uploads"][-len(stores):])}
    uploads = []
    for u in rec["uploads"]:
        reps = [{"mins": np.asarray(r["mins"]),
                 "checksums": {c: np.asarray(v)
                               for c, v in r["checksums"].items()}}
                for r in u["replicas"]]
        store = resident.get(id(u))
        if store is not None:
            for rep, r in zip(reps, store.replicas):
                rep["cols"] = {c: np.asarray(v) for c, v in r.cols.items()}
        uploads.append((u["chunk"], reps, np.asarray(u["bad_counts"])))
    del stores, resident
    counts, n_wrong = compare(state, uploads, refs)
    return {k: (v, 0) for k, v in counts.items()}, n_wrong


def release(state: State):
    gc.collect()


def control(state: State, rec: dict) -> tuple[dict, int]:
    """The comparison applied to the control (``reference.control_replica``
    in the program's place), one whole upload of each chunk."""
    refs = references(state)
    ctrl = references(state, make=reference.control_replica)
    uploads = [(i, reps, n_bad) for i, (reps, n_bad) in enumerate(ctrl)]
    return compare(state, uploads, refs)
