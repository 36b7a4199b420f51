"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def index_search(mins: jax.Array, lo, hi) -> jax.Array:
    """mins (blocks, n_parts) sorted -> (blocks, 2) [p_first, p_last]."""
    first = jnp.maximum(
        jnp.sum(mins < lo, axis=-1).astype(jnp.int32) - 1, 0)
    last = jnp.maximum(
        jnp.sum(mins <= hi, axis=-1).astype(jnp.int32) - 1, 0)
    return jnp.stack([first, last], axis=-1)


def pax_scan(key_col: jax.Array, proj: jax.Array, lo, hi):
    """key_col (rows,), proj (rows, n_proj) -> (mask, masked_proj, count)."""
    mask = (key_col >= lo) & (key_col <= hi)
    out = jnp.where(mask[:, None], proj, 0)
    return mask, out, mask.sum(dtype=jnp.int32)


def hail_read(mins, keys, proj, bad, use_index, lo, hi, *,
              partition_size: int):
    """Fused split-reader oracle: per-block root lookup + pruned range scan.

    mins (B,P), keys (B,R), proj (B,C,R), bad (B,R) bool, use_index (B,)
    -> (mask (B,R) bool, masked proj, rows_read_frac (B,) f32)."""
    rows = keys.shape[1]
    pr = index_search(mins, lo, hi)                          # (B, 2)
    r0 = jnp.where(use_index > 0, pr[:, 0] * partition_size, 0)
    r1 = jnp.where(use_index > 0,
                   jnp.minimum((pr[:, 1] + 1) * partition_size, rows), rows)
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    in_range = (r >= r0[:, None]) & (r < r1[:, None])
    mask = (keys >= lo) & (keys <= hi) & in_range & ~bad
    out = jnp.where(mask[:, None, :], proj, 0)
    frac = (r1 - r0).astype(jnp.float32) / rows
    return mask, out, frac


def hail_read_batch(mins, keys, proj, bad, use_index, lohi, *,
                    partition_size: int):
    """Shared-scan batch oracle: Q range queries over one split at once.

    lohi (Q, 2) -> (mask (B, Q, R) bool, proj masked by the union of the Q
    masks (B, C, R), rows_read_frac (B, Q) f32) — the Q=1 slice matches
    ``hail_read`` exactly."""

    def one(lo, hi):
        m, _, f = hail_read(mins, keys, proj, bad, use_index, lo, hi,
                            partition_size=partition_size)
        return m, f

    mask_q, frac_q = jax.vmap(one)(lohi[:, 0], lohi[:, 1])   # (Q,B,R) (Q,B)
    mask = jnp.moveaxis(mask_q, 0, 1)                        # (B, Q, R)
    out = jnp.where(mask.any(axis=1)[:, None, :], proj, 0)
    return mask, out, jnp.moveaxis(frac_q, 0, -1)


def selective_scan(delta, x, b, c, a):
    """Naive mamba1 recurrence oracle.  delta,x (B,T,D); b,c (B,T,N);
    a (D,N) negative. -> y (B,T,D), h_final (B,D,N)."""

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp              # (B,D) (B,D) (B,N) (B,N)
        at = jnp.exp(dt_t[..., None] * a)      # (B,D,N)
        bt = (dt_t * x_t)[..., None] * b_t[:, None, :]
        h = at * h + bt
        y = (h * c_t[:, None, :]).sum(-1)      # (B,D)
        return h, y

    bs, t, d = delta.shape
    h0 = jnp.zeros((bs, d, a.shape[-1]), jnp.float32)
    inp = (delta.swapaxes(0, 1), x.swapaxes(0, 1),
           b.swapaxes(0, 1), c.swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h0, inp)
    return ys.swapaxes(0, 1), h


def attention(q, k, v, *, causal: bool = True, window: int | None = None):
    """q (B,T,H,D), k/v (B,S,KV,D) -> (B,T,H,D). fp32 softmax oracle."""
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, t, kvh, rep, d).astype(jnp.float32)
    sc = jnp.einsum("btgrk,bsgk->bgrts", qg, k.astype(jnp.float32))
    sc = sc / math.sqrt(d)
    qp = jnp.arange(t)[:, None]
    kp = jnp.arange(s)[None, :]
    m = jnp.ones((t, s), bool)
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    sc = jnp.where(m, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bgrts,bsgk->btgrk", w, v.astype(jnp.float32))
    return out.reshape(b, t, h, d).astype(q.dtype)
