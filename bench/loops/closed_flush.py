"""Closed loop of analysts over ``HailServer``.

Set-up makes the table on the device from the seed, uploads it through the
program's HAIL upload into the configuration's replicas, starts a server
with the configuration's settings, and warms it with ``warm_rounds``
rounds of the mix drawn from a salted stream, through ``submit`` and
``flush`` alone: every round has the window's shape, so they compile what
the window runs and fill the block cache, however the server plans and
batches.

The window: each of the mix's clients has one query outstanding.  The
server takes submissions between flushes, so each round submits every
client's next query (``QueryStream.next_round``) and flushes; a query's
latency runs from its submit to the moment its rows are on the host (the
flush's start plus ``FlushStats.query_done_s``).  Rounds start until ``seconds`` have passed;
the window ends when the last round's flush returns, and every rate is
over the whole window.  After the window every answer is compared with
the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import datagen, querygen, reference, roofline
from bench.stats import nearest_rank


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    cols: dict
    bad: np.ndarray
    store: object
    server: object
    stream: querygen.QueryStream
    log: object


def to_hail(q: querygen.Query):
    from repro.core.query import HailQuery
    return HailQuery(filter=(q.column, q.lo, q.hi), projection=q.projection)


def _round(server, stream):
    """Submit one query per client and flush.  -> (the flush's start, its
    stats, per client (query, ticket, submit time) or None where refused)."""
    import jax
    from repro.runtime.jobserver import AdmissionError
    sent = []
    with jax.profiler.TraceAnnotation("bench:submit"):
        for c, q in enumerate(stream.next_round()):
            t_sub = time.perf_counter()
            try:
                ticket = server.submit(to_hail(q), tenant=f"client{c}")
            except AdmissionError:
                sent.append(None)
                continue
            sent.append((q, ticket, t_sub))
    with jax.profiler.TraceAnnotation("bench:flush"):
        t_flush = time.perf_counter()
        fs = server.flush()
    return t_flush, fs, sent


def _warm(server, warm_stream, rounds: int, log):
    """Rounds of the mix's own shape, drawn from a salted stream, through
    the server's public entry points: they compile every program the
    window's rounds run and fill the block cache, however the server plans
    and batches them."""
    t = time.perf_counter()
    for _ in range(rounds):
        _, _, sent = _round(server, warm_stream)
        for item in sent:
            if item is None:
                raise RuntimeError("warm-up query refused by admission")
            if item[1].status != "done":
                raise RuntimeError(f"warm-up query failed: {item[1].error}")
    log(f"warm-up: {rounds} rounds of {warm_stream.clients} queries in "
        f"{time.perf_counter() - t:.3f} s")


def setup(ctx) -> State:
    from repro.core import upload as up
    from repro.runtime.jobserver import HailServer, ServerConfig
    from bench.harness import program_schema

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    schema = program_schema(cfg)
    t = time.perf_counter()
    text, cols, bad = datagen.make_table(cfg, ctx.seed)
    ctx.log(f"data: {len(bad)} rows made on the device in "
            f"{time.perf_counter() - t:.3f} s, {int(bad.sum())} bad")
    t = time.perf_counter()
    store, _ = up.hail_upload(schema, text, index_columns=cfg["replicas"],
                              partition_size=cfg["partition_size"])
    del text
    ctx.log(f"upload: {store.nbytes} PAX bytes over {len(store.replicas)} "
            f"replicas in {time.perf_counter() - t:.3f} s")
    server = HailServer(store, ServerConfig(**cfg.get("server", {})))
    stream = querygen.QueryStream(traffic, cfg, cols, bad, ctx.seed)
    _warm(server,
          querygen.QueryStream(traffic, cfg, cols, bad, ctx.seed, salt=1),
          int(traffic["warm_rounds"]), ctx.log)
    return State(cfg, traffic, cols, bad, store, server, stream, ctx.log)


def window(state: State, seconds: float) -> dict:
    server, stream = state.server, state.stream
    latencies, answers, flushes = [], [], []
    attempted = unanswered = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        t_flush, fs, sent = _round(server, stream)
        attempted += len(sent)
        served = []
        for item in sent:
            if item is None or item[1].status != "done":
                unanswered += 1
                continue
            q, ticket, t_sub = item
            latencies.append(t_flush - t_sub
                             + fs.query_done_s[ticket.ticket_id])
            answers.append((q, ticket.result.rows))
            served.append((q, ticket.result.from_cache))
        flushes.append((fs, served))
    window_s = time.perf_counter() - t0
    e2e = {"qps": len(latencies) / window_s}
    if latencies:
        e2e["p50_ms"] = nearest_rank(latencies, 50) * 1e3
        e2e["p90_ms"] = nearest_rank(latencies, 90) * 1e3
    state.log(f"closed loop: {len(flushes)} flushes, {len(latencies)} "
              f"answers, {unanswered} unanswered")
    return {"window_s": window_s, "attempted": attempted,
            "failed": unanswered, "e2e": e2e,
            "answers": answers, "flushes": flushes}


def compare(state: State, answers) -> tuple[dict, int]:
    """Sum of rows missing or extra and of projected values that differ,
    over ``answers`` [(query, rows)], and the count of wrong answers."""
    wrong_rows = wrong_values = n_wrong = 0
    for q, rows in answers:
        want = reference.answer(state.cols, state.bad, q)
        r, v = reference.compare_answer(state.cols, want, rows, q.projection)
        wrong_rows += r
        wrong_values += v
        n_wrong += bool(r or v)
    return {"wrong_rows": wrong_rows, "wrong_values": wrong_values}, n_wrong


def check(state: State, rec: dict) -> tuple[dict, int]:
    counts, n_wrong = compare(state, rec["answers"])
    counts["unanswered"] = rec["failed"]
    counts["no_answer_checked"] = int(not rec["answers"])
    if "trace" in rec:
        clustered = set(state.cfg["replicas"])
        rec["least_bytes"] = sum(
            roofline.least_bytes([q for q, hit in served if not hit],
                                 state.cols, state.bad, clustered)
            for _, served in rec["flushes"])
    return {k: (v, 0) for k, v in counts.items()}, n_wrong


def release(state: State):
    state.server = state.store = None
    gc.collect()


def control(state: State, rec: dict) -> tuple[dict, int]:
    """The comparison applied to the control (``reference.control_answer``
    in the program's place) on the window's own queries."""
    cfg = state.cfg
    rows, part = cfg["rows_per_block"], cfg["partition_size"]
    index = {}
    answers = []
    for q, _ in rec["answers"]:
        if q.column not in index:
            index[q.column] = reference.partition_index(
                state.cols, state.bad, q.column, rows, part)
        ids = reference.control_answer(state.bad, q, index[q.column], rows,
                                       part)
        got = {c: state.cols[c][ids] for c in q.projection}
        got[reference.ROWID] = ids
        answers.append((q, got))
    return compare(state, answers)
