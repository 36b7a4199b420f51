"""Fused HAIL record-reader Pallas kernel: ONE dispatch per split — and,
since the HailServer, ONE dispatch per (split, query-batch).

This is HailSplitting (paper §4.3) applied inside the TPU runtime.  The
per-block pipeline used to be two kernels + a Python loop — ``index_search``
over the root directories, then one ``pax_scan`` launch per block.  That
re-created the exact per-task overhead the paper kills (3,200 map tasks ->
~20 splits, Fig 6c): every block paid a kernel dispatch, and every new
query range paid a recompile because (lo, hi) were baked in as Python ints.

Here the whole split is a single jitted program: the root-directory lookup
(a popcount over the partition minima per block and query) and a single
``pallas_call`` with a 2D grid over ``(block, row_tile)``:

* the query ranges and each (block, query)'s qualifying row range are
  RUNTIME scalar-prefetch arrays in SMEM, so one compiled reader serves
  every query — and every BATCH of Q concurrent queries — against the same
  store shape, with zero per-query recompiles.  Q is static (it shapes the
  mask output), so a server batching at a fixed ``max_batch`` compiles one
  extra variant per distinct batch size, once;
* each grid step evaluates ALL Q range predicates against the one key tile
  it already loaded — the shared-scan win: Q concurrent range queries over
  a split cost one dispatch and one pass over the data instead of Q;
* row tiles outside EVERY query's partition range are PRUNED: predicated
  via ``pl.when``, they write zeros and skip the predicate/projection work
  (the index-scan I/O win, expressed as skipped compute per tile);
* per-block ``use_index`` flags let one dispatch serve MIXED splits — blocks
  whose chosen replica has a matching clustered index scan only their
  partition range, failover blocks full-scan — so the re-planned retry
  splits of a failed node run through the same fused kernel;
* outputs: a PER-QUERY qualifying mask (bad rows excluded), the projection
  masked by the UNION of the query masks (rows no query wants stay zero;
  each query recovers its own rows via its mask), and per-(block, query)
  rows-read fractions feeding the I/O cost model.

Layout (Mosaic tiles the last two dims of every block in (8, 128) units):
a block's R rows are viewed lane-dense as (R/128, 128) — a free reshape of
the row-major column — so a row tile is a (sublanes, 128) slab.  The
projection is column-major, (B, C, R), and the per-query mask is emitted as
int8 (B, Q, R), so neither puts a small C or Q in the lane dim.  Blocks whose
row count is not a multiple of 128 are viewed as one (1, R) slab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _reader_kernel(lohi_ref, rng_ref, keys_ref, proj_ref, bad_ref,
                   mask_ref, out_ref, *, n_q: int, tile_sub: int, lanes: int):
    b = pl.program_id(0)
    tile_rows = tile_sub * lanes
    tile_lo = pl.program_id(1) * tile_rows

    r0s, r1s = [], []
    live_any = False
    for qi in range(n_q):
        r0 = rng_ref[(b * n_q + qi) * 2]
        r1 = rng_ref[(b * n_q + qi) * 2 + 1]
        r0s.append(r0)
        r1s.append(r1)
        live_any = live_any | ((tile_lo < r1) & (tile_lo + tile_rows > r0))

    # --- row-tile scan, pruned when the tile is dead for EVERY query -------
    @pl.when(live_any)
    def _():
        keys = keys_ref[...]                                 # (TS, L)
        good = ~bad_ref[...]
        r = (tile_lo
             + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0) * lanes
             + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1))
        any_m = jnp.zeros(keys.shape, jnp.bool_)
        for qi in range(n_q):
            m = ((keys >= lohi_ref[2 * qi]) & (keys <= lohi_ref[2 * qi + 1])
                 & (r >= r0s[qi]) & (r < r1s[qi]) & good)
            mask_ref[qi] = m.astype(mask_ref.dtype)
            any_m = any_m | m
        out_ref[...] = jnp.where(any_m[None], proj_ref[...], 0)

    @pl.when(~live_any)                                      # pruned tile
    def _():
        mask_ref[...] = jnp.zeros(mask_ref.shape, mask_ref.dtype)
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def row_ranges(mins, use_index, lohi, *, partition_size: int, rows: int):
    """Root-directory lookup for every (block, query): the half-open row
    range [r0, r1) an index scan must read — the whole block where
    ``use_index`` is 0.  The first partition read is the last whose min is
    < lo (keys equal to lo may end the partition before one whose min is
    lo), the last is the last whose min is <= hi.  mins (B, P), use_index
    (B,), lohi (Q, 2) -> r0, r1 (B, Q) int32."""
    def count(v, below):                                     # (Q,) -> (B, Q)
        m = mins[:, None, :]
        v = v[None, :, None]
        return jnp.sum(m < v if below else m <= v, axis=-1, dtype=jnp.int32)

    use = use_index[:, None] > 0
    p_first = jnp.maximum(count(lohi[:, 0], True) - 1, 0)
    p_last = jnp.maximum(count(lohi[:, 1], False) - 1, 0)
    r0 = jnp.where(use, p_first * partition_size, 0)
    r1 = jnp.where(use, jnp.minimum((p_last + 1) * partition_size, rows),
                   rows)
    return r0.astype(jnp.int32), r1.astype(jnp.int32)


def _tile_sublanes(sub: int, want: int) -> int:
    """Largest divisor of ``sub`` that is <= ``want`` and a multiple of 32
    (the int8 mask's sublane tile); the whole ``sub`` when it fits."""
    if sub <= want:
        return sub
    for ts in range(want - want % 32, 0, -32):
        if sub % ts == 0:
            return ts
    return sub


def hail_read_batch(mins: jax.Array, keys: jax.Array, proj: jax.Array,
                    bad: jax.Array, use_index: jax.Array, lohi: jax.Array, *,
                    partition_size: int, interpret: bool,
                    row_tile: int = 32768):
    """Fused shared-scan reader — one program for all blocks of a split
    AND all Q queries of a batch.

    mins (B, P) int32       per-block root directories (ignored where
                            ``use_index`` is 0)
    keys (B, R) int32       filter column, replica-chosen per block
    proj (B, C, R)          projection columns (+rowid), same replicas
    bad  (B, R) bool        bad-record positions per block
    use_index (B,) int32    1 = clustered index matches -> partition pruning
    lohi (Q, 2) int32       RUNTIME per-query (lo, hi) ranges

    -> (mask (B, Q, R) bool — per-query match masks,
        proj masked by the union of the Q masks (B, C, R),
        rows_read_frac (B, Q) f32)
    """
    b, rows = keys.shape
    c = proj.shape[1]
    n_q = lohi.shape[0]
    lanes = LANES if rows % LANES == 0 else rows
    sub = rows // lanes
    ts = _tile_sublanes(sub, max(row_tile // lanes, 1))
    lohi = jnp.asarray(lohi, jnp.int32)
    r0, r1 = row_ranges(mins, use_index, lohi,
                        partition_size=partition_size, rows=rows)
    kernel = functools.partial(_reader_kernel, n_q=n_q, tile_sub=ts,
                               lanes=lanes)
    rows_spec = pl.BlockSpec((None, ts, lanes), lambda i, t, *_: (i, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, sub // ts),
        in_specs=[
            rows_spec,
            pl.BlockSpec((None, c, ts, lanes), lambda i, t, *_: (i, 0, t, 0)),
            rows_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, n_q, ts, lanes),
                         lambda i, t, *_: (i, 0, t, 0)),
            pl.BlockSpec((None, c, ts, lanes), lambda i, t, *_: (i, 0, t, 0)),
        ],
    )
    mask, out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_q, sub, lanes), jnp.int8),
            jax.ShapeDtypeStruct((b, c, sub, lanes), proj.dtype),
        ],
        interpret=interpret,
    )(lohi.reshape(-1), jnp.stack([r0, r1], axis=-1).reshape(-1),
      keys.reshape(b, sub, lanes), proj.reshape(b, c, sub, lanes),
      bad.reshape(b, sub, lanes))
    frac = (r1 - r0).astype(jnp.float32) / rows
    return (mask.reshape(b, n_q, rows).astype(jnp.bool_),
            out.reshape(b, c, rows), frac)


def hail_read(mins: jax.Array, keys: jax.Array, proj: jax.Array,
              bad: jax.Array, use_index: jax.Array, lo, hi, *,
              partition_size: int, interpret: bool):
    """Single-query fused split reader: the Q=1 case of ``hail_read_batch``.

    -> (mask (B, R) bool, masked proj (B, C, R), rows_read_frac (B,) f32)
    """
    lohi = jnp.stack([jnp.asarray(lo, jnp.int32),
                      jnp.asarray(hi, jnp.int32)]).reshape(1, 2)
    mask, out, frac = hail_read_batch(mins, keys, proj, bad, use_index, lohi,
                                      partition_size=partition_size,
                                      interpret=interpret)
    return mask[:, 0], out, frac[:, 0]
