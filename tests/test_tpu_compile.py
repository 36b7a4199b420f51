"""The served path's device programs compile for a TPU v5e.

Each test compiles (``interpret=False``, through Mosaic) for a described
``v5e:2x2`` topology at deployment shapes — 2^19-row blocks, 1024-row
partitions — without a chip attached: the fused reader for Q = 1 and 8,
the served program that also splits its outputs per column and query,
the block sort behind adaptive builds and repair, and the shard_map'd
reader over a four-chip mesh.  Nothing runs; a passing compile is not a
chip run.  The topology is described inside a fixture, never at import.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.hail_reader import hail_read_batch

ROWS = 2 ** 19
PART = 1024


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent cache off: what is
    compiled for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _reader_args(sharding, b, c, n_q, lohi_sharding=None):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    return (sds((b, ROWS // PART), jnp.int32), sds((b, ROWS), jnp.int32),
            sds((b, c, ROWS), jnp.int32), sds((b, ROWS), jnp.bool_),
            sds((b,), jnp.int32),
            jax.ShapeDtypeStruct((n_q, 2), jnp.int32,
                                 sharding=lohi_sharding or sharding))


@pytest.mark.parametrize("c,n_q", [(2, 1), (5, 8)])
def test_fused_reader_compiles_for_v5e(topo, c, n_q):
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(functools.partial(hail_read_batch, partition_size=PART,
                                   interpret=False))
    compiled = fn.lower(*_reader_args(one_chip, 2, c, n_q)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * c * ROWS * 4


@pytest.mark.parametrize("n_q", [1, 8])
def test_split_reader_compiles_for_v5e(topo, n_q):
    """The served reader's one program, which returns each of the 20
    projected columns and each query's mask, fraction and bytes."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    c = 20
    with ops.stats_scope(merge=False):
        lowered = ops._hail_read_batch_jit.lower(
            *_reader_args(one_chip, 2, c, n_q), reader=ops.hail_read_batch,
            partition_size=PART, interpret=False)
    out = lowered.out_info
    assert [a.shape for a in out.cols] == [(2, ROWS)] * c
    assert [a.dtype for a in out.masks] == [jnp.bool_] * n_q
    assert [a.shape for a in out.fracs] == [(2,)] * n_q
    assert [a.shape for a in out.bytes_read] == [()] * n_q
    assert out.shared_bytes.shape == ()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * (4 * c + n_q) * ROWS


def test_sort_block_compiles_for_v5e(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    cols = {f"c{i}": sds((2, ROWS), jnp.int32) for i in range(10)}
    compiled = jax.jit(ops.sort_block).lower(
        sds((2, ROWS), jnp.int32), cols).compile()
    assert "sort" in compiled.as_text()


def test_sharded_reader_compiles_for_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    fn = ops._sharded_batch_reader(mesh, ("data",), PART, False)
    with ops.stats_scope(merge=False):
        compiled = fn.lower(*_reader_args(
            NamedSharding(mesh, P("data")), 4, 4, 2,
            lohi_sharding=NamedSharding(mesh, P()))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # each chip holds its own block tile: one block of every operand
    assert mem.argument_size_in_bytes < 2 * (4 + 4 * 4 + 1) * ROWS


def test_placed_upload_pass_compiles_for_v5e(topo):
    """One pass of the placed upload on a chip: the client's parse of
    ``PASS_BLOCKS`` home blocks and a datanode's sort, root and checksums
    of as many blocks of one replica.  Their temporaries stay a small part
    of the chip beside the 3 GB of PAX and 2.3 GB of text it holds."""
    from repro.core import schema as sc
    from repro.core import upload as up
    one_chip = SingleDeviceSharding(topo.devices[1])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    b, width = up.PASS_BLOCKS, 91
    parse = up._parse_pipeline(sc.USERVISITS).lower(
        sds((b, ROWS, width), jnp.uint8), sds((b,), jnp.int32)).compile()
    cols = {c: sds((b, ROWS), jnp.int32)
            for c in sc.USERVISITS.names + ("__rowid__",)}
    index = up._index_pipeline(PART).lower(
        cols, sds((b, ROWS), jnp.bool_), cols["visitDate"]).compile()
    assert "sort" in index.as_text()
    for compiled in (parse, index):
        mem = compiled.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < 3e9, mem
