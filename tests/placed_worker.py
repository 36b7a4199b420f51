"""Subprocess worker for ``test_placed_store.py``: a HAIL store placed over
four virtual CPU devices, against the one-device store of the same text
and a numpy oracle.  The device count is fixed when JAX starts, and the
test process must see one device, so this runs on its own:

    python tests/placed_worker.py

Prints one JSON line per check, ``{"check": name, "ok": bool, ...}``.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

from repro.core import query as q  # noqa: E402
from repro.core import schema as sc  # noqa: E402
from repro.core import upload as up  # noqa: E402
from repro.core.parse import format_rows, parse_block  # noqa: E402
from repro.core.schema import ROWID  # noqa: E402
from repro.core.store import PlacedBlocks, assign_nodes  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.runtime.jobserver import HailServer, ServerConfig  # noqa: E402

ROWS, BLOCKS, PART, NODES = 256, 12, 64, 4
KEYS = ("visitDate", "sourceIP", "adRevenue")
CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


def table():
    import jax
    cols = sc.gen_uservisits(ROWS * BLOCKS, seed=3)
    raw = format_rows(sc.USERVISITS, cols,
                      bad_fraction=0.01).reshape(BLOCKS, ROWS, -1)
    bad = np.asarray(jax.jit(jax.vmap(
        lambda r: parse_block(sc.USERVISITS, r)[1]))(raw)).reshape(-1)
    return cols, raw, bad


def split_text(raw, devices):
    """The text with each block on its home chip (a run of blocks each)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    return jax.device_put(raw, NamedSharding(
        Mesh(np.array(devices), ("chips",)), P("chips")))


def upload(raw, devices=None):
    text = raw if devices is None or len(devices) == 1 else split_text(
        raw, devices)
    store, _ = up.hail_upload(sc.USERVISITS, text, KEYS, partition_size=PART,
                              n_nodes=NODES, devices=devices)
    return store


def upload_in_passes(raw, devices, pass_blocks):
    saved, up.PASS_BLOCKS = up.PASS_BLOCKS, pass_blocks
    try:
        return upload(raw, devices)
    finally:
        up.PASS_BLOCKS = saved


def queries(cols, bad):
    good = np.flatnonzero(~bad)
    ip = int(cols["sourceIP"][good[7]])
    return [
        q.HailQuery(("visitDate", 7300, 7460), ("sourceIP",)),
        q.HailQuery(("visitDate", 9000, 9900), ("sourceIP",)),
        q.HailQuery(("sourceIP", ip, ip),
                    ("searchWord", "duration", "adRevenue")),
        q.HailQuery(("adRevenue", 1000, 2700),
                    ("searchWord", "duration", "adRevenue")),
        # every row: the answer is exactly the good rows
        q.HailQuery(("adRevenue", 0, 2 ** 31 - 2),
                    ("searchWord", "duration", "adRevenue")),
    ]


def serve(store, qs):
    server = HailServer(store, ServerConfig(result_cache=False))
    tickets = [server.submit(x) for x in qs]
    server.flush()
    return [t.result.rows for t in tickets]


def oracle(cols, bad, query):
    c, lo, hi = query.filter
    ids = np.flatnonzero((cols[c] >= lo) & (cols[c] <= hi) & ~bad)
    return {ROWID: ids, **{p: cols[p][ids] for p in query.projection}}


def same_rows(got, want) -> bool:
    order = np.argsort(got[ROWID], kind="stable")
    return all(np.array_equal(np.asarray(got[c])[order], want[c])
               for c in want)


@check
def placement(ctx):
    """Each chip holds exactly the block-replicas assign_nodes gives it."""
    store, devices = ctx["placed"], ctx["devices"]
    nodes = assign_nodes(BLOCKS, len(KEYS), NODES)
    for r, rep in enumerate(store.replicas):
        for arr in [rep.mins, *rep.cols.values(), *rep.checksums.values()]:
            assert isinstance(arr, PlacedBlocks)
            for k, part in enumerate(arr.parts):
                assert part.devices() == {devices[k]}
                want = np.flatnonzero(nodes[r] % len(devices) == k)
                assert sorted(arr.blocks_on(k)) == list(want)
                assert part.shape[0] == len(want)
        for b in range(BLOCKS):
            assert rep.cols[ROWID][np.array([b])].devices() == {
                devices[(b + r) % 4]}
    assert len({(b + r) % 4 for r in range(3)
                for b in range(BLOCKS)}) == 4
    return {}


@check
def bit_equal_upload(ctx):
    """Every replica, block by block, equals the one-device upload, in
    one pass and in passes of two blocks a chip."""
    one = ctx["one"]
    for store in (ctx["placed"], ctx["placed_passes"]):
        assert np.array_equal(store.bad_counts, np.asarray(one.bad_counts))
        assert store.nbytes == one.nbytes
        for a, b in zip(one.replicas, store.replicas):
            assert a.sort_key == b.sort_key
            for blk in range(BLOCKS):
                sel = np.array([blk])
                assert np.array_equal(np.asarray(a.mins[blk]),
                                      np.asarray(b.mins[sel])[0])
                for c in a.cols:
                    assert np.array_equal(np.asarray(a.cols[c][blk]),
                                          np.asarray(b.cols[c][sel])[0])
                    assert np.array_equal(np.asarray(a.checksums[c][blk]),
                                          np.asarray(b.checksums[c][sel])[0])
    return {}


@check
def key_ranges(ctx):
    """Each replica's host key range table, read per chip at upload, is
    every block's min and max good key, as on the one-device store."""
    cols, bad = ctx["cols"], ctx["bad"]
    for store in (ctx["placed"], ctx["placed_passes"]):
        for r, rep in enumerate(store.replicas):
            assert rep.key_range.shape == (BLOCKS, 2)
            for b in range(BLOCKS):
                rows = slice(b * ROWS, (b + 1) * ROWS)
                good = cols[rep.sort_key][rows][~bad[rows]]
                assert tuple(rep.key_range[b]) == (good.min(), good.max())
            assert np.array_equal(rep.key_range,
                                  ctx["one"].replicas[r].key_range)
    return {}


@check
def answers(ctx):
    """HailServer's answers from the placed store equal the oracle's and
    the one-device store's (range, point, bad rows, 1 and 3 columns)."""
    qs = queries(ctx["cols"], ctx["bad"])
    placed = serve(ctx["placed"], qs)
    one = serve(ctx["one"], qs)
    for x, got, ref in zip(qs, placed, one):
        want = oracle(ctx["cols"], ctx["bad"], x)
        assert same_rows(got, want) and same_rows(ref, want), x
    return {"rows": [len(g[ROWID]) for g in placed]}


@check
def reads_stay_on_their_chip(ctx):
    """Every reader program's inputs and outputs sit on the split's chip,
    and a flush moves nothing between devices."""
    import jax
    devices, store = ctx["devices"], ctx["placed"]
    seen = []
    orig = ops.hail_read_batch_split

    def recording(mins, keys, proj, bad, use_index, lohi, **kw):
        out = orig(mins, keys, proj, bad, use_index, lohi, **kw)
        devs = set()
        for a in (mins, keys, proj, bad, *jax.tree.leaves(out)):
            devs |= a.devices()
        seen.append(devs)
        return out
    ops.hail_read_batch_split = recording
    tracer = obs_trace.install()
    try:
        server = HailServer(store, ServerConfig(result_cache=False))
        for x in queries(ctx["cols"], ctx["bad"]):
            server.submit(x)
        with jax.transfer_guard_device_to_device("disallow_explicit"):
            stats = server.flush()
    finally:
        ops.hail_read_batch_split = orig
        obs_trace.uninstall()
    chips = [ev["args"]["chip"] for ev in tracer.events
             if ev.get("name") == "issue" and ev["ph"] == "B"]
    assert len(chips) == len(seen) == stats.n_splits > 0
    assert all(devs == {devices[k]} for devs, k in zip(seen, chips))
    assert set(chips) == set(range(4))
    assert sum(stats.chip_blocks.values()) > 0
    # the issue order goes round the chips
    assert chips[:4] == [0, 1, 2, 3], chips
    return {"splits": stats.n_splits}


@check
def failover_reads_other_chips(ctx):
    """With one datanode dead, its blocks are read from their other
    replicas, on other chips, and the answers stay exact."""
    store = ctx["placed"]
    qs = queries(ctx["cols"], ctx["bad"])
    store.namenode.kill_node(1)
    try:
        got = serve(store, qs)
        plan = q.plan(store, qs[0])
        assert 1 not in set(plan.nodes.tolist())
    finally:
        store.namenode.revive(1)
    for x, rows in zip(qs, got):
        assert same_rows(rows, oracle(ctx["cols"], ctx["bad"], x)), x
    return {}


@check
def one_chip_is_todays_store(ctx):
    """One chip (one device given) gives the one-chip store: plain arrays,
    no placement, bit-equal to the upload without devices."""
    import jax
    store = upload(ctx["raw"], [ctx["devices"][0]])
    assert store.devices == () and store.n_chips == 1
    for a, b in zip(ctx["one"].replicas, store.replicas):
        for c in a.cols:
            assert isinstance(b.cols[c], jax.Array)
            assert np.array_equal(np.asarray(a.cols[c]),
                                  np.asarray(b.cols[c]))
        assert np.array_equal(np.asarray(a.mins), np.asarray(b.mins))
    return {}


@check
def placed_store_refuses_rewrites_and_cross_chip_reads(ctx):
    store = ctx["placed"]
    try:
        store.replicas[0].cols[ROWID][np.array([0, 1])]
    except ValueError:
        pass
    else:
        raise AssertionError("a read across chips was served")
    for call in (store.repair_blocks, lambda: store.demote_replica(0),
                 store.add_replica):
        try:
            call()
        except NotImplementedError:
            continue
        raise AssertionError(f"{call} rewrote a placed store")
    return {}


def main():
    import jax
    assert jax.device_count() == 4, jax.device_count()
    cols, raw, bad = table()
    devices = jax.devices()
    ctx = {"cols": cols, "raw": raw, "bad": bad, "devices": devices,
           "one": upload(raw), "placed": upload(raw, devices),
           "placed_passes": upload_in_passes(raw, devices, 2)}
    for fn in CHECKS:
        try:
            extra = fn(ctx)
            print(json.dumps({"check": fn.__name__, "ok": True, **extra}),
                  flush=True)
        except Exception:
            print(json.dumps({"check": fn.__name__, "ok": False,
                              "error": traceback.format_exc()[-2000:]}),
                  flush=True)


if __name__ == "__main__":
    main()
