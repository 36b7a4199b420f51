"""Tables made on the device from ``--seed``, in one jitted call.

A configuration file lists its columns, each uniform over ``[lo, hi)``,
with the width of its fixed-width decimal text (zero-padded digits; a row
is its columns' digits and a newline).  ``bad_fraction`` of the rows get
one byte, drawn over the row's digit positions, replaced by ``x``: those
are the bad records, and the set is the generator's, not the parser's.

What comes back: the text blocks on the device (what the program uploads),
and the columns and the bad-row mask on the host (what the reference
reads).  Nothing of the program is imported here.
"""
from __future__ import annotations

import functools

import numpy as np


def _seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 64 bits."""
    import jax
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2^64)")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


@functools.lru_cache(maxsize=None)
def _generator(n_blocks: int, rows: int, domains: tuple, widths: tuple,
               bad_fraction: float):
    import jax
    import jax.numpy as jnp

    n_cols = len(domains)
    row_width = sum(widths) + 1

    def one_block(key):
        ks = jax.random.split(key, n_cols + 2)
        vals = [jax.random.randint(ks[i], (rows,), lo, hi, jnp.int32)
                for i, (lo, hi) in enumerate(domains)]
        parts = []
        for v, w in zip(vals, widths):
            powers = jnp.asarray([10 ** (w - 1 - i) for i in range(w)],
                                 jnp.int32)
            parts.append(((v[:, None] // powers[None, :]) % 10
                          + ord("0")).astype(jnp.uint8))
        parts.append(jnp.full((rows, 1), ord("\n"), jnp.uint8))
        text = jnp.concatenate(parts, axis=1)
        if bad_fraction > 0:
            bad = jax.random.uniform(ks[n_cols], (rows,)) < bad_fraction
            pos = jax.random.randint(ks[n_cols + 1], (rows,), 0,
                                     row_width - 1)
            hit = bad[:, None] & (jnp.arange(row_width)[None, :]
                                  == pos[:, None])
            text = jnp.where(hit, jnp.uint8(ord("x")), text)
        else:
            bad = jnp.zeros((rows,), bool)
        return text, jnp.stack(vals), bad

    @jax.jit
    def make(key):
        keys = jax.random.split(key, n_blocks)
        return jax.lax.map(one_block, keys)

    return make


def make_table(cfg: dict, seed: int, n_blocks: int | None = None):
    """-> (text (B, R, W) uint8 on the device, {column: (B*R,) int32} on the
    host, bad (B*R,) bool on the host)."""
    import jax
    n_blocks = cfg["n_blocks"] if n_blocks is None else n_blocks
    rows = cfg["rows_per_block"]
    domains = tuple((int(c["lo"]), int(c["hi"])) for c in cfg["columns"])
    widths = tuple(int(c["ascii_width"]) for c in cfg["columns"])
    for (lo, hi), w in zip(domains, widths):
        if not (0 <= lo < hi <= 2 ** 31 - 1 and hi - 1 < 10 ** w):
            raise ValueError(f"domain [{lo}, {hi}) does not fit {w} digits")
    make = _generator(n_blocks, rows, domains, widths,
                      float(cfg["bad_fraction"]))
    text, vals, bad = make(_seed_key(seed))
    vals_h = np.asarray(vals)                  # (B, C, R)
    cols = {c["name"]: np.ascontiguousarray(vals_h[:, i, :]).reshape(-1)
            for i, c in enumerate(cfg["columns"])}
    bad_h = np.asarray(bad).reshape(-1)
    del vals, bad
    return text, cols, bad_h
