"""Closed loop of analysts over a HAIL cluster of one datanode a chip.

Set-up makes each chip's home blocks on that chip (``datagen.make_table``
with the chip as JAX's default device, from the seed and the chip's index
folded into one seed, ``seed * chips + chip``), joins the host columns in
global block order (chip k's home blocks are the k-th run of blocks), and
uploads the text through the program's placed HAIL upload
(``hail_upload(..., devices=)``): each block is parsed where it lives and
its three replicas are sorted and indexed on the chips of three different
datanodes (``n_nodes`` of the configuration).  It then starts
``HailServer`` on the placed store and warms it as ``closed_flush`` does.

A program whose ``hail_upload`` takes no ``devices`` cannot place a store
over chips: set-up refuses it at once, before any data is made.

The window, the check against the reference, the control and the release
are ``closed_flush``'s.  The window runs with every device-to-device
transfer refused (``jax.transfer_guard_device_to_device``): a served
program that moved data between chips would fail the run.
"""
from __future__ import annotations

import inspect
import pathlib
import time

import numpy as np

from bench import datagen, harness, querygen

_closed = harness.load_module(
    pathlib.Path(__file__).with_name("closed_flush.py"),
    "bench_loop_closed_flush")
check, control, release = _closed.check, _closed.control, _closed.release


def _peaks(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def make_cluster_table(cfg: dict, seed: int, devices: list):
    """-> (text (n_blocks, R, W) split over ``devices`` by blocks, host
    columns and bad mask in global block order)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    n_chips = len(devices)
    if cfg["n_blocks"] % n_chips:
        raise ValueError(f"{cfg['n_blocks']} blocks do not divide over "
                         f"{n_chips} chips")
    texts, cols, bads = [], [], []
    for k, dev in enumerate(devices):
        with jax.default_device(dev):
            text, c, b = datagen.make_table(
                cfg, seed * n_chips + k, n_blocks=cfg["n_blocks"] // n_chips)
        texts.append(jax.device_put(text, dev))
        cols.append(c)
        bads.append(b)
    sharding = NamedSharding(Mesh(np.array(devices), ("chips",)),
                             PartitionSpec("chips"))
    text = jax.make_array_from_single_device_arrays(
        (cfg["n_blocks"],) + texts[0].shape[1:], sharding, texts)
    return (text, {c: np.concatenate([p[c] for p in cols]) for c in cols[0]},
            np.concatenate(bads))


def setup(ctx) -> "_closed.State":
    import jax
    from repro.core import upload as up
    from repro.runtime.jobserver import HailServer, ServerConfig

    if "devices" not in inspect.signature(up.hail_upload).parameters:
        raise TypeError("hail_upload takes no devices=: this program "
                        "cannot place a store over chips")
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    schema = harness.program_schema(cfg)
    devices = jax.devices()[:ctx.cell.chips]
    t = time.perf_counter()
    text, cols, bad = make_cluster_table(cfg, ctx.seed, devices)
    ctx.log(f"data: {len(bad)} rows made on {len(devices)} chips in "
            f"{time.perf_counter() - t:.3f} s, {int(bad.sum())} bad")
    t = time.perf_counter()
    store, _ = up.hail_upload(schema, text, index_columns=cfg["replicas"],
                              partition_size=cfg["partition_size"],
                              n_nodes=cfg["n_nodes"], devices=devices)
    del text
    ctx.log(f"upload: {store.nbytes} PAX bytes over {len(store.replicas)} "
            f"replicas on {store.n_chips} chips in "
            f"{time.perf_counter() - t:.3f} s; peak bytes a chip "
            f"{_peaks(devices)}")
    server = HailServer(store, ServerConfig(**cfg.get("server", {})))
    stream = querygen.QueryStream(traffic, cfg, cols, bad, ctx.seed)
    _closed._warm(
        server, querygen.QueryStream(traffic, cfg, cols, bad, ctx.seed,
                                     salt=1),
        int(traffic["warm_rounds"]), ctx.log)
    ctx.log(f"set-up: peak bytes a chip {_peaks(devices)}")
    return _closed.State(cfg, traffic, cols, bad, store, server, stream,
                         ctx.log)


def window(state, seconds: float) -> dict:
    import jax
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        rec = _closed.window(state, seconds)
    state.log(f"window: peak bytes a chip {_peaks(state.store.devices)}")
    return rec
