"""Index-search Pallas kernel: the clustered index's root-directory lookup.

For a batch of blocks, each with a VMEM-resident root of sorted partition
minima, find [p_first, p_last] for a (lo, hi) range (paper Fig 2 steps 1+2):
p_first is the last partition whose min is < lo (keys equal to lo may end
the partition before one whose min is lo), p_last the last whose min is
<= hi.  Roots are sorted, so each searchsorted is a popcount — one VPU
reduction instead of a serial binary search (TPU adaptation: data-parallel
counting beats branchy log-time search on a vector unit).

Grid tiles the block axis; (lo, hi) are RUNTIME scalars in SMEM, so one
compiled kernel serves every query range.  The fused split reader
(hail_reader.py) makes the same lookup in XLA before its pallas_call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _search_kernel(lohi_ref, mins_ref, out_ref):
    lo = lohi_ref[0, 0]
    hi = lohi_ref[0, 1]
    mins = mins_ref[...]                                    # (TB, P)
    first = jnp.maximum(jnp.sum(mins < lo, axis=1).astype(jnp.int32) - 1, 0)
    last = jnp.maximum(jnp.sum(mins <= hi, axis=1).astype(jnp.int32) - 1, 0)
    out_ref[...] = jnp.stack([first, last], axis=1)


def index_search(mins: jax.Array, lo, hi,
                 *, block_tile: int = 8,
                 interpret: bool | None = None) -> jax.Array:
    """mins (blocks, n_parts) sorted rows -> (blocks, 2) int32.
    lo/hi may be python ints or traced values (no per-query recompile)."""
    blocks, n_parts = mins.shape
    tb = min(block_tile, blocks)
    while blocks % tb:
        tb -= 1
    lohi = jnp.asarray([lo, hi], jnp.int32).reshape(1, 2)
    return pl.pallas_call(
        _search_kernel,
        grid=(blocks // tb,),
        in_specs=[pl.BlockSpec((1, 2), lambda b: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((tb, n_parts), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((tb, 2), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, 2), jnp.int32),
        interpret=interpret_default() if interpret is None else interpret,
    )(lohi, mins)
