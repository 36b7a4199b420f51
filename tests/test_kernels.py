"""Per-kernel allclose vs pure-jnp oracles: shape/dtype sweeps + hypothesis,
plus end-to-end kernel-backed record-reader equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hail_reader import hail_read_batch
from repro.kernels.index_search import index_search
from repro.kernels.pax_scan import pax_scan

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# sort_block: the one stable sort behind upload, builds, demotion and repair
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("blocks,n", [(1, 64), (4, 256), (2, 1000)])
def test_sort_block_shapes(blocks, n):
    keys = jax.random.randint(KEY, (blocks, n), -1000, 1000, dtype=jnp.int32)
    payload = jnp.arange(blocks * n, dtype=jnp.int32).reshape(blocks, n)
    sk, cols, perm = ops.sort_block(keys, {"p": payload})
    np.testing.assert_array_equal(np.asarray(sk), np.sort(np.asarray(keys), 1))
    np.testing.assert_array_equal(
        np.asarray(perm), np.argsort(np.asarray(keys), 1, kind="stable"))
    np.testing.assert_array_equal(
        np.asarray(cols["p"]),
        np.take_along_axis(np.asarray(payload), np.asarray(perm), 1))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([32, 128, 96]))
def test_sort_block_property(seed, n):
    """Many ties: the permutation is the STABLE one, identical to the eager
    upload's ``index.sort_permutation`` — a rebuilt block reproduces an
    uploaded one byte for byte."""
    from repro.core import index as idx
    r = np.random.default_rng(seed)
    keys = jnp.asarray(r.integers(-5, 5, (2, n)).astype(np.int32))
    _, _, perm = ops.sort_block(keys, {})
    want = jax.vmap(idx.sort_permutation)(keys)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(perm), np.argsort(np.asarray(keys), 1, kind="stable"))


# ---------------------------------------------------------------------------
# index_search
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("blocks,parts", [(3, 8), (16, 32), (5, 64)])
def test_index_search_shapes(blocks, parts):
    mins = jnp.sort(jax.random.randint(KEY, (blocks, parts), 0, 10_000,
                                       dtype=jnp.int32), axis=1)
    got = index_search(mins, 500, 7000)
    want = ref.index_search(mins, 500, 7000)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 10_000), st.integers(0, 2**31 - 1))
def test_index_search_property(lo, hi, seed):
    lo, hi = min(lo, hi), max(lo, hi)
    r = np.random.default_rng(seed)
    mins = jnp.asarray(np.sort(r.integers(0, 10_000, (4, 16)), 1).astype(np.int32))
    got = np.asarray(index_search(mins, lo, hi))
    want = np.asarray(ref.index_search(mins, lo, hi))
    np.testing.assert_array_equal(got, want)
    # semantic: returned row range covers every qualifying row
    for b in range(4):
        lo_r, hi_r = got[b]
        assert lo_r <= hi_r


# ---------------------------------------------------------------------------
# pax_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,cols,tile", [(512, 1, 128), (1024, 4, 256),
                                            (2048, 3, 1024)])
def test_pax_scan_shapes(rows, cols, tile):
    kc = jax.random.randint(KEY, (rows,), 0, 1000, dtype=jnp.int32)
    pj = jax.random.randint(KEY, (rows, cols), 0, 99, dtype=jnp.int32)
    m, o, c = pax_scan(kc, pj, 200, 700, row_tile=tile)
    rm, ro, rc = ref.pax_scan(kc, pj, 200, 700)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ro))
    assert int(c.sum()) == int(rc)


def test_pax_scan_dtypes():
    kc = jax.random.randint(KEY, (256,), 0, 1000, dtype=jnp.int32)
    for dt in (jnp.int32, jnp.float32):
        pj = jax.random.randint(KEY, (256, 2), 0, 99).astype(dt)
        m, o, c = pax_scan(kc, pj, 0, 500, row_tile=128)
        rm, ro, rc = ref.pax_scan(kc, pj, 0, 500)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(ro))


# ---------------------------------------------------------------------------
# hail_reader: the fused shared-scan reader vs its jnp oracle
# ---------------------------------------------------------------------------


def _reader_inputs(seed, b, rows, c, n_q, part):
    r = np.random.default_rng(seed)
    keys = np.sort(r.integers(0, 1000, (b, rows)), 1).astype(np.int32)
    return (jnp.asarray(keys[:, ::part]), jnp.asarray(keys),
            jnp.asarray(r.integers(-99, 99, (b, c, rows)).astype(np.int32)),
            jnp.asarray(r.random((b, rows)) < 0.01),
            jnp.asarray(np.arange(b) % 2, jnp.int32),   # index + full scans
            jnp.asarray(np.sort(r.integers(0, 1000, (n_q, 2)), 1), jnp.int32))


def _assert_reader_matches_oracle(args, part, row_tile):
    got = hail_read_batch(*args, partition_size=part, interpret=True,
                          row_tile=row_tile)
    want = ref.hail_read_batch(*args, partition_size=part)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("b,rows,c,n_q,part,row_tile", [
    (3, 64, 2, 1, 32, 32768),        # rows not a multiple of 128: (1, R)
    (2, 1024, 3, 3, 128, 32768),     # one lane-dense tile per block
    (2, 8192, 2, 8, 1024, 4096),     # two tiles: pruning between them
])
def test_fused_reader_matches_oracle(b, rows, c, n_q, part, row_tile):
    args = _reader_inputs(rows, b, rows, c, n_q, part)
    _assert_reader_matches_oracle(args, part, row_tile)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 5]))
def test_fused_reader_property(seed, n_q):
    args = _reader_inputs(seed, 2, 8192, 2, n_q, 512)
    _assert_reader_matches_oracle(args, 512, 4096)


@pytest.mark.parametrize("lo,hi", [(1, 1), (2, 7), (0, 10)])
def test_index_scan_keeps_keys_spanning_partitions(lo, hi):
    """A key equal to lo can end the partition BEFORE the first partition
    whose min is lo; the lookup must start there or those rows are lost
    (first seen at 2^19-row blocks, where each visitDate repeats ~100x)."""
    from repro.core import index as idx
    part = 128
    keys = np.repeat(np.arange(11, dtype=np.int32), 100)[:1024][None]
    want = (keys >= lo) & (keys <= hi)
    mins = jnp.asarray(keys[:, ::part])
    got = idx.index_scan_mask(jnp.asarray(keys[0]), mins[0], lo, hi, part)
    np.testing.assert_array_equal(np.asarray(got), want[0])
    mask, _, _ = hail_read_batch(
        mins, jnp.asarray(keys), jnp.asarray(keys)[:, None],
        jnp.zeros(keys.shape, bool), jnp.ones((1,), jnp.int32),
        jnp.asarray([[lo, hi]], jnp.int32), partition_size=part,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(mask[:, 0]), want)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,s,h,kv,d", [(128, 128, 4, 4, 32),
                                        (256, 256, 4, 2, 64),
                                        (128, 256, 8, 2, 32)])
def test_flash_attention_shapes(t, s, h, kv, d):
    q = jax.random.normal(KEY, (2, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, s, kv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, s, kv, d))
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 32, 128])
def test_flash_attention_masks(window):
    q = jax.random.normal(KEY, (1, 256, 2, 32), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 256, 2, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 256, 2, 32))
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q = jax.random.normal(KEY, (1, 128, 4, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 128, 2, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 128, 2, 64), jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


# ---------------------------------------------------------------------------
# selective_scan (fused mamba1 recurrence)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,d,n,chunk,dblk", [(32, 16, 8, 8, 8),
                                              (64, 32, 4, 16, 16),
                                              (48, 8, 8, 16, 8)])
def test_selective_scan_shapes(t, d, n, chunk, dblk):
    from repro.kernels.selective_scan import selective_scan
    ks = [jax.random.fold_in(KEY, i) for i in range(5)]
    delta = jax.nn.softplus(jax.random.normal(ks[0], (2, t, d), jnp.float32))
    x = jax.random.normal(ks[1], (2, t, d), jnp.float32)
    b = jax.random.normal(ks[2], (2, t, n), jnp.float32)
    c = jax.random.normal(ks[3], (2, t, n), jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[4], (d, n), jnp.float32) * 0.3)
    y, h = selective_scan(delta, x, b, c, a, chunk=chunk, d_block=dblk)
    ry, rh = ref.selective_scan(delta, x, b, c, a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(rh),
                               atol=1e-4, rtol=1e-4)


def test_selective_scan_matches_mamba1_layer_math():
    """The kernel computes the same recurrence the mamba1 layer uses."""
    from repro.kernels.selective_scan import selective_scan
    from repro.models import mamba as mb
    t, d, n = 16, 8, 4
    delta = jax.nn.softplus(jax.random.normal(KEY, (1, t, d)))
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (1, t, d))
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (1, t, n))
    c = jax.random.normal(jax.random.fold_in(KEY, 3), (1, t, n))
    a = -jnp.exp(jnp.zeros((d, n)))
    aa = jnp.exp(delta[..., None] * a)
    bb = (delta * x)[..., None] * b[:, :, None, :]
    h_all = mb._m1_scan_chunk(jnp.zeros((1, d, n)), aa, bb)
    want = jnp.einsum("btdn,btn->btd", h_all, c)
    got, _ = selective_scan(delta, x, b, c, a, chunk=8, d_block=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end: kernel-backed record reader == jnp record reader
# ---------------------------------------------------------------------------


def test_record_reader_kernel_equivalence(hail_store):
    from repro.core import query as q
    query = q.HailQuery(filter=("visitDate", 7305, 7670),
                        projection=("sourceIP",))
    qp = q.plan(hail_store, query)
    a = q.read_hail(hail_store, query, qp)
    b = q.read_hail_kernels(hail_store, query, qp)
    am = np.asarray(a.mask)
    bm = np.asarray(b.mask)
    np.testing.assert_array_equal(am, bm)
    np.testing.assert_array_equal(np.asarray(a.cols["sourceIP"])[am],
                                  np.asarray(b.cols["sourceIP"])[bm])
