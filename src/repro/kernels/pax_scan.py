"""PAX range-scan Pallas kernel — the HailRecordReader inner loop (§4.3).

Streams partitions of a PAX block HBM->VMEM: for each row tile, evaluate the
clustered-key range predicate, emit the qualifying mask, the masked
projection columns, and a per-tile qualifying count (the caller's compaction
/ tuple-reconstruction gather uses the mask).  The caller passes only the
partition range [row_start, row_end) the index lookup selected — the kernel
never touches the rest of the block (that is the index-scan I/O win).

Grid: (row_tiles,); key tile (TR,) and projection tile (TR, C) in VMEM;
(lo, hi) are RUNTIME scalars in SMEM — one compiled kernel serves every
query range (the fused split reader in hail_reader.py subsumes this kernel
for whole-split reads; this stays as the single-block primitive).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _scan_kernel(lohi_ref, key_ref, proj_ref, mask_ref, out_ref, cnt_ref):
    lo = lohi_ref[0, 0]
    hi = lohi_ref[0, 1]
    keys = key_ref[...]                       # (TR,)
    m = (keys >= lo) & (keys <= hi)
    mask_ref[...] = m
    out_ref[...] = jnp.where(m[:, None], proj_ref[...], 0)
    cnt_ref[0] = m.sum(dtype=jnp.int32)


def pax_scan(key_col: jax.Array, proj: jax.Array, lo, hi,
             *, row_tile: int = 1024, interpret: bool | None = None):
    """key_col (rows,), proj (rows, C) -> (mask (rows,), masked proj, counts).
    lo/hi may be python ints or traced values (no per-query recompile).
    """
    rows = key_col.shape[0]
    c = proj.shape[1]
    tr = min(row_tile, rows)
    while rows % tr:
        tr -= 1
    lohi = jnp.asarray([lo, hi], jnp.int32).reshape(1, 2)
    mask, out, cnt = pl.pallas_call(
        _scan_kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((tr,), lambda i: (i,)),
                  pl.BlockSpec((tr, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tr,), lambda i: (i,)),
                   pl.BlockSpec((tr, c), lambda i: (i, 0)),
                   pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((rows,), jnp.bool_),
                   jax.ShapeDtypeStruct((rows, c), proj.dtype),
                   jax.ShapeDtypeStruct((rows // tr,), jnp.int32)],
        interpret=interpret_default() if interpret is None else interpret,
    )(lohi, key_col, proj)
    return mask, out, cnt
