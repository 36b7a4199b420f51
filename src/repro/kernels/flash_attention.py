"""Flash attention (forward) Pallas kernel — the serving-prefill hot spot.

Tiled online-softmax attention with causal and sliding-window masking.
Grid (batch*kv_heads*rep, q_tiles, kv_tiles): the kv axis is the innermost
(sequential on TPU) grid dimension; running max/denominator/accumulator live
in VMEM scratch across kv steps and the output tile is written on the last
step.  Block sizes are MXU-aligned (multiples of 128 on the seq dims).

GQA is handled by indexing: program (b, g, r) reads q head g*rep+r and kv
head g — no materialized head repetition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, window,
                  block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                  # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                       # (BQ, BK)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + p @ v
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q (B,T,H,D), k/v (B,S,KV,D), H = KV*rep -> (B,T,H,D)."""
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    assert h == kvh * rep
    bq = min(block_q, t)
    bk = min(block_k, s)
    assert t % bq == 0 and s % bk == 0, (t, bq, s, bk)

    # layout: programs over (b*h); q head g*rep+r maps to kv head g
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)

    grid = (b * h, t // bq, s // bk)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal,
        window=window, block_q=bq, block_k=bk, n_k=s // bk)

    def kv_index(bh, qi, ki):
        # program bh = batch*h + head; its kv row is batch*kvh + head//rep
        return ((bh // h) * kvh + (bh % h) // rep, ki, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denominator
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret_default() if interpret is None else interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
