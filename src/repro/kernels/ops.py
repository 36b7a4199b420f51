"""jit'd wrappers around the Pallas kernels.

Interpret mode follows the platform: kernels run in the Pallas interpreter
only where JAX's default backend is the CPU, and lower through Mosaic
everywhere else — no environment knob, no silent fallback.  The
kernel-backed record readers (core.query.read_hail_kernels /
read_hail_batch) call through these wrappers and are asserted equivalent
to the jnp reader by the system test suite, so kernel/oracle agreement is
exercised end-to-end, not only by per-kernel tests.

Dispatch/recompile accounting: every wrapper that backs the record reader
bumps ``DISPATCH_COUNTS`` per call and ``TRACE_COUNTS`` per retrace (a
Python side effect inside the traced body runs only when jit actually
recompiles).  ``reader_stats()`` / ``reset_stats()`` expose them; the
no-recompile acceptance tests and bench_kernels' BENCH_kernels.json
regression-guard the counts.  (lo, hi) are traced arguments everywhere —
new query ranges reuse the compiled readers.
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checksum as _ck
from repro.core import index as _idx
from repro.kernels import interpret_default
from repro.kernels.hail_reader import hail_read as _hail_read
from repro.kernels.hail_reader import hail_read_batch
from repro.obs import trace as _obs_trace

_INTERPRET: bool | None = None       # None: follow the platform

DISPATCH_COUNTS: collections.Counter = collections.Counter()
TRACE_COUNTS: collections.Counter = collections.Counter()


def interpret_mode() -> bool:
    """True when the readers run in the Pallas interpreter: by default
    exactly when JAX's default backend is the CPU."""
    return interpret_default() if _INTERPRET is None else _INTERPRET


def set_interpret(on: bool | None):
    """Override interpret mode at runtime (``None`` follows the platform
    again).  The mode is a static argument of the jitted readers, so a flip
    compiles a fresh variant instead of reusing the other mode's.
    Interpret mode is refused off the CPU: it would hide the device."""
    global _INTERPRET
    if on and not interpret_default():
        raise ValueError("interpret mode runs kernels on the host; refused "
                         f"on backend {jax.default_backend()!r}")
    _INTERPRET = None if on is None else bool(on)


def reset_stats():
    DISPATCH_COUNTS.clear()
    TRACE_COUNTS.clear()


def reader_stats() -> dict:
    return {"dispatches": dict(DISPATCH_COUNTS),
            "traces": dict(TRACE_COUNTS)}


class StatsScope:
    """Handle yielded by ``stats_scope`` — holds the scope's counters so
    assertions can also run after the ``with`` block exits."""

    def __init__(self, dispatches: collections.Counter,
                 traces: collections.Counter):
        self.dispatches = dispatches
        self.traces = traces


@contextlib.contextmanager
def stats_scope(merge: bool = True):
    """Isolated dispatch/trace counters for one test or measurement block.

    Swaps FRESH counters into the module globals on entry and restores the
    previous ones on exit (merging the scope's counts back in unless
    ``merge=False``), so dispatch-count assertions see only the calls made
    inside the scope — independent of test order — instead of relying on
    module-global ``reset_stats`` mutation racing other tests.

        with ops.stats_scope() as s:
            q.read_hail_kernels(store, query, qp)
        assert s.dispatches["hail_read"] == 1

    Note: trace counts are still a property of jit's process-wide cache — a
    scope observes a retrace only if compilation actually happens inside it.
    """
    global DISPATCH_COUNTS, TRACE_COUNTS
    prev_d, prev_t = DISPATCH_COUNTS, TRACE_COUNTS
    DISPATCH_COUNTS = collections.Counter()
    TRACE_COUNTS = collections.Counter()
    scope = StatsScope(DISPATCH_COUNTS, TRACE_COUNTS)
    try:
        yield scope
    finally:
        if merge:
            prev_d.update(scope.dispatches)
            prev_t.update(scope.traces)
        DISPATCH_COUNTS, TRACE_COUNTS = prev_d, prev_t


@jax.jit
def sort_block(keys: jax.Array, cols: dict[str, jax.Array]):
    """Stable sort of each block by key, permuting all PAX columns: the same
    stable XLA sort the eager upload runs (``core.index.sort_permutation``),
    so adaptive builds, demotions and repairs reproduce an upload's layout
    byte for byte.  keys (blocks, n) -> (sorted_keys, permuted cols, perm)."""
    perm = jax.vmap(_idx.sort_permutation)(keys)
    return (jnp.take_along_axis(keys, perm, axis=1),
            {c: jnp.take_along_axis(v, perm, axis=1) for c, v in cols.items()},
            perm)


# -- jitted entry points: lo/hi TRACED, shapes/statics are the only cache keys


@functools.partial(jax.jit, static_argnames=("partition_size", "interpret"))
def _hail_read_jit(mins, keys, proj, bad, use_index, lo, hi,
                   *, partition_size, interpret):
    TRACE_COUNTS["hail_read"] += 1
    return _hail_read(mins, keys, proj, bad, use_index, lo, hi,
                      partition_size=partition_size, interpret=interpret)


class BatchRead(NamedTuple):
    """One split of a shared scan, already split per column and query:
    ``cols`` C projected columns (B, R) in their stored dtype, ``masks`` Q
    (B, R) bool, ``fracs`` Q rows-read fractions (B,) f32, ``bytes_read`` Q
    modeled bytes and ``shared_bytes`` the batch's physical bytes (per
    block, the widest fraction), each a 0-d f32."""
    cols: tuple
    masks: tuple
    fracs: tuple
    bytes_read: tuple
    shared_bytes: jax.Array


@functools.partial(jax.jit,
                   static_argnames=("reader", "partition_size", "interpret"))
def _hail_read_batch_jit(mins, keys, proj, bad, use_index, lohi,
                         *, reader, partition_size, interpret) -> BatchRead:
    """``reader`` (static) and the split of its outputs as ONE program.

    Modeled bytes are 4 B per row read for the key and for each of the
    C - 1 projected attributes (the C-th column, the row id, is not read):
    4 R C per block read whole.  A query's figure sums its fractions, the
    shared one each block's widest."""
    TRACE_COUNTS["hail_read_batch"] += 1
    mask, out, frac = reader(mins, keys, proj, bad, use_index, lohi,
                             partition_size=partition_size,
                             interpret=interpret)
    n_cols, rows = out.shape[1], out.shape[2]
    col_bytes = 4 * rows
    fracs = tuple(frac[:, qi] for qi in range(frac.shape[1]))
    return BatchRead(
        cols=tuple(out[:, j] for j in range(n_cols)),
        masks=tuple(mask[:, qi] for qi in range(mask.shape[1])),
        fracs=fracs,
        bytes_read=tuple(f.sum() * col_bytes * n_cols for f in fracs),
        shared_bytes=frac.max(axis=1).sum() * col_bytes * n_cols)


@jax.jit
def _verify_blocks_jit(data, sums):
    TRACE_COUNTS["verify_blocks"] += 1
    return _ck.verify_blocks(data, sums)


@functools.partial(jax.jit, static_argnames=("partition_size",))
def _verify_root_jit(mins, keys, *, partition_size):
    TRACE_COUNTS["verify_root"] += 1
    return _ck.verify_root(mins, keys, partition_size)


def verify_blocks(data, sums) -> jax.Array:
    """Batched chunk-checksum verify: data (C, B, rows) int32 columns
    stacked, sums (C, B, chunks) uint32 -> bool (C, B).  ONE dispatch per
    call; the read path calls it once per BlockCache fill, so verification
    cost amortizes across cache hits.  ``verify_block_cols`` counts the
    (col, block) pairs proven, for the clean-path overhead guard."""
    DISPATCH_COUNTS["verify_blocks"] += 1
    DISPATCH_COUNTS["verify_block_cols"] += int(data.shape[0] * data.shape[1])
    _obs_trace.instant("verify_blocks", track="kernels", cat="dispatch",
                       args={"cols": int(data.shape[0]),
                             "blocks": int(data.shape[1])})
    return _verify_blocks_jit(data, sums)


def verify_root(mins, keys, *, partition_size: int) -> jax.Array:
    """Root-directory consistency check (mins vs sorted key column)."""
    DISPATCH_COUNTS["verify_root"] += 1
    return _verify_root_jit(mins, keys, partition_size=partition_size)


def hail_read(mins, keys, proj, bad, use_index, lo, hi, *,
              partition_size: int):
    """Fused split reader: ONE dispatch per call (== per split).

    ``use_index`` should be a HOST (numpy) array: the per-block scan-mode
    counters read it before it ships to the device, so the non-blocking
    dispatch path stays free of device->host syncs.  (Per-filter-column
    attribution — ``index_scan_blocks[col]`` etc. — is the record readers'
    job via ``governor.attribute_read``, which writes the same
    ``DISPATCH_COUNTS``; this wrapper only knows shapes, not columns.)"""
    DISPATCH_COUNTS["hail_read"] += 1
    # adaptive-convergence tests assert full_scan_blocks hits 0
    u = np.asarray(use_index)        # no-op for the host-array callers
    n_idx = int(u.astype(bool).sum())
    DISPATCH_COUNTS["index_scan_blocks"] += n_idx
    DISPATCH_COUNTS["full_scan_blocks"] += u.shape[0] - n_idx
    _obs_trace.instant("hail_read", track="kernels", cat="dispatch",
                       args={"index_blocks": n_idx,
                             "full_blocks": int(u.shape[0]) - n_idx})
    return _hail_read_jit(mins, keys, proj, bad, jnp.asarray(u, jnp.int32),
                          jnp.asarray(lo, jnp.int32),
                          jnp.asarray(hi, jnp.int32),
                          partition_size=partition_size,
                          interpret=interpret_mode())


def hail_read_batch_split(mins, keys, proj, bad, use_index, lohi, *,
                          partition_size: int) -> BatchRead:
    """Fused shared-scan read of one split: ONE dispatch per (split,
    query-batch), returning each column's and each query's arrays
    (``BatchRead``), so the caller launches no device program after it.

    ``lohi`` is the batch's (Q, 2) runtime lo/hi array; Q is a SHAPE, so a
    server batching at a fixed ``max_batch`` compiles one variant per
    distinct batch size (counted in ``traces``) and reuses it for every
    later batch of that size.  ``use_index`` and ``lohi`` go to the program
    as host arrays.  The scan-mode counters charge each of the Q
    queries with the blocks it logically scanned — serially-equivalent
    accounting, so adaptive/governor invariant tests see the same totals
    whether traffic was batched or not.  Per-column attribution stays the
    record readers' job (``governor.attribute_read``, once per query).

    The program wraps ``hail_read_batch`` (the kernel's traceable entry,
    -> mask (B, Q, R), union-masked projection (B, C, R), fractions (B, Q))
    as this module holds it at call time, keyed on that function: a reader
    swapped in at run time (the benchmark's fault checks wrap it) compiles
    its own program."""
    DISPATCH_COUNTS["hail_read"] += 1
    DISPATCH_COUNTS["hail_read_batch"] += 1
    lohi = np.asarray(lohi, np.int32).reshape(-1, 2)
    n_q = lohi.shape[0]
    u = np.asarray(use_index, np.int32)  # host array: counters cost no sync
    n_idx = int(np.count_nonzero(u))
    DISPATCH_COUNTS["index_scan_blocks"] += n_q * n_idx
    DISPATCH_COUNTS["full_scan_blocks"] += n_q * (u.shape[0] - n_idx)
    return _hail_read_batch_jit(mins, keys, proj, bad, u, lohi,
                                reader=hail_read_batch,
                                partition_size=partition_size,
                                interpret=interpret_mode())


@functools.lru_cache(maxsize=None)
def _sharded_batch_reader(mesh, axes: tuple, partition_size: int,
                          interpret: bool):
    """shard_map'd fused batch reader, compiled once per (mesh, axes,
    partition_size, interpret mode)."""
    from jax.sharding import PartitionSpec as P
    spec = P(axes if len(axes) > 1 else axes[0])

    def local(mins, keys, proj, bad, use_index, lohi):
        TRACE_COUNTS["hail_read_sharded"] += 1
        return hail_read_batch(mins, keys, proj, bad, use_index, lohi,
                               partition_size=partition_size,
                               interpret=interpret)

    # block dim sharded over the scan axes; the (Q, 2) ranges replicated.
    # check_vma=False: outputs are per-shard block tiles, no replication
    # invariant for the checker to prove through the pallas call.
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, spec, spec, P()),
                       out_specs=(spec, spec, spec), check_vma=False)
    return jax.jit(fn)


def hail_read_batch_sharded(mins, keys, proj, bad, use_index, lohi, *,
                            partition_size: int, mesh, axes,
                            n_splits: int = 1):
    """Sharded fused reader: ONE dispatch per WAVE of up to n_dev splits.

    The leading (block) dim must equal ``n_dev * blocks_per_device`` — the
    wave assembler in core.query pads ragged splits with dead blocks and
    stacks them — and is shard_map'd over ``axes`` of ``mesh``, so every
    device scans its own split's block tile against the same replicated
    (Q, 2) ranges.  Per-device fused dispatches therefore equal the wave
    count = ceil(splits / n_dev).  Scan-mode counters are the CALLER's job
    (only it knows which blocks are padding); this wrapper counts waves
    and the splits they carry."""
    axes = tuple(axes)
    DISPATCH_COUNTS["hail_read_sharded_waves"] += 1
    DISPATCH_COUNTS["hail_read_sharded_splits"] += int(n_splits)
    _obs_trace.instant("hail_read_sharded", track="kernels", cat="dispatch",
                       args={"splits": int(n_splits),
                             "blocks": int(mins.shape[0]),
                             "axes": ",".join(axes)})
    fn = _sharded_batch_reader(mesh, axes, partition_size, interpret_mode())
    lohi = np.asarray(lohi, np.int32).reshape(-1, 2)
    return fn(mins, keys, proj, bad,
              jnp.asarray(np.asarray(use_index), jnp.int32),
              jnp.asarray(lohi))
