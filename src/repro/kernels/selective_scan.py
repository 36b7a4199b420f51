"""Fused Mamba1 selective-scan Pallas kernel.

§Perf iteration 8 showed the software formulations of the per-channel SSM
recurrence are HBM-bound either way on the XLA path: the associative scan
touches every (B,T,d,N) element O(log Tc) times, and a serial lax.scan pays
transposes + autodiff residuals.  The TPU-native answer mirrors the CUDA
kernel the Mamba authors wrote: FUSE the recurrence — stream (delta, x, B,
C) tiles HBM->VMEM once, keep the (d_block, N) state resident in VMEM
across the whole sequence, expand a_t/b_t in registers, and write only y
(and the final state) back.  HBM traffic drops from O(T*d*N*log Tc) to the
irreducible O(T*(2d + 2N)) input + O(T*d) output stream.

Grid: (batch, d_blocks, n_chunks); the chunk axis is innermost/sequential,
carrying the state scratch.  Time steps inside a chunk run in a
fori_loop over VMEM-resident tiles — the dependency chain is hidden by the
(d_block, N) lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _scan_kernel(delta_ref, x_ref, b_ref, c_ref, a_ref, y_ref, hout_ref,
                 h_scr, *, tc: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_log = a_ref[...]                       # (dblk, N) = -exp(A_log)
    delta = delta_ref[0]                     # (Tc, dblk)
    x = x_ref[0]                             # (Tc, dblk)
    bmat = b_ref[0]                          # (Tc, N)
    cmat = c_ref[0]                          # (Tc, N)

    def step(t, carry):
        h, y = carry
        dt_t = delta[t][:, None]             # (dblk, 1)
        at = jnp.exp(dt_t * a_log)           # (dblk, N)
        bt = (dt_t * x[t][:, None]) * bmat[t][None, :]
        h = at * h + bt
        y = y.at[t].set((h * cmat[t][None, :]).sum(axis=1))
        return h, y

    y0 = jnp.zeros(y_ref.shape[1:], jnp.float32)
    h, y = jax.lax.fori_loop(0, tc, step, (h_scr[...], y0))
    y_ref[0] = y.astype(y_ref.dtype)
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _done():
        hout_ref[0] = h.astype(hout_ref.dtype)


def selective_scan(delta: jax.Array, x: jax.Array, b: jax.Array,
                   c: jax.Array, a: jax.Array, *, chunk: int = 64,
                   d_block: int = 128, interpret: bool | None = None):
    """Mamba1 recurrence  h_t = exp(delta_t * A) h_{t-1} + delta_t B_t x_t,
    y_t = (h_t * C_t).sum(-1).

    delta, x: (B, T, D) f32; b, c: (B, T, N) f32; a: (D, N) f32 (negative).
    Returns y (B, T, D), h_final (B, D, N).
    """
    bs, t, d = delta.shape
    n = b.shape[-1]
    tc = min(chunk, t)
    while t % tc:
        tc -= 1
    dblk = min(d_block, d)
    while d % dblk:
        dblk -= 1
    n_chunks = t // tc
    grid = (bs, d // dblk, n_chunks)
    kernel = functools.partial(_scan_kernel, tc=tc, n_chunks=n_chunks)
    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tc, dblk), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, tc, dblk), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, tc, n), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, tc, n), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((dblk, n), lambda bi, di, ci: (di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tc, dblk), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, dblk, n), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bs, t, d), delta.dtype),
                   jax.ShapeDtypeStruct((bs, d, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dblk, n), jnp.float32)],
        interpret=interpret_default() if interpret is None else interpret,
    )(delta, x, b, c, a)
    return y, h_final
