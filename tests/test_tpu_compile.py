"""Native (Mosaic) compiles of the engine kernels for a described TPU v5e.

Interpret mode cannot see what the TPU compiler refuses: unaligned slices,
gathers, operand types the MXU lacks, VMEM overruns.  These tests compile
every engine kernel with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology (no chip attached) at realistic widths, and check
that the kernel is really in the program (``tpu_custom_call``).  Nothing
runs, so they say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.segment_reduce import (
    segment_minmax_mxu, segment_sum_counts_mxu, segment_sum_mxu,
)
from repro.kernels.sort_u32 import SORT_TILE, sort_lex_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described chip's executables cannot be read back: keep them out
    of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n", [SORT_TILE, 1 << 16],
                         ids=["single_tile", "multi_tile"])
def test_sort_lex(one_chip, n):
    lane = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    _compile(lambda hi, lo: sort_lex_pallas(hi, lo, interpret=False),
             lane, lane)


@pytest.mark.parametrize("n", [SORT_TILE, 1 << 16],
                         ids=["single_tile", "multi_tile"])
def test_sort_lex_tagged(one_chip, n):
    lane = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    _compile(lambda hi, lo, tag: sort_lex_pallas(
        hi, lo, interpret=False, tag=tag, tag_bits=2), lane, lane, lane)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_sum(one_chip, dtype):
    n, d, k = 1 << 16, 8, 100_000
    seg = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((n, d), dtype, sharding=one_chip)
    _compile(lambda s, v: segment_sum_mxu(s, v, k, out_dtype=dtype,
                                          interpret=False), seg, vals)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_sum_counts(one_chip, dtype):
    n, k = 1 << 16, 32_769
    seg = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((n, 1), dtype, sharding=one_chip)
    _compile(lambda s, v: segment_sum_counts_mxu(s, v, k, out_dtype=dtype,
                                                 interpret=False), seg, vals)


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segment_minmax(one_chip, kind, dtype):
    n, d, k = 1 << 16, 1, 131_073
    seg = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((n, d), dtype, sharding=one_chip)
    _compile(lambda s, v: segment_minmax_mxu(kind, s, v, k, interpret=False),
             seg, vals)


@pytest.mark.parametrize("kind", ["sum", "min"])
def test_segment_reduce_of_map_output(one_chip, kind, monkeypatch):
    """The engine's Reduce input through the dispatcher: a [records,
    fanout] Map output flattened to a row count that is no multiple of the
    row tile (SSSP's structure carries one extra root record)."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")   # native kernels
    records, fanout, k = (1 << 15) + 1, 16, 1 << 15
    grid = lambda dt: jax.ShapeDtypeStruct((records, fanout), dt,
                                           sharding=one_chip)
    _compile(lambda s, v, m: ops.segment_reduce(
        kind, s.reshape(-1), {"v": v.reshape(-1)}, m.reshape(-1), k,
        backend="pallas"), grid(jnp.int32), grid(jnp.float32),
        grid(jnp.bool_))


def test_merge_at_benchmark_bucket_fits_one_chip(one_chip, monkeypatch):
    """The whole merge (sort, last writer, route, reduce) at the chip
    benchmark's bucket: 2^23 rows of wordcount's [rows, 1] value column
    into 1,024 key slots.  The compiler refuses a program past the chip's
    memory, where the padded layout of a [rows, 1] column would take it if
    it spread to the producers of the reduce's ids and mask.  The merge's
    ``valid`` and ``sign`` ride through the sort in its index lane, so no
    gather of a one-byte lane through the permutation is left."""
    from repro.core.incremental import _merge_reduce
    from repro.core.kvstore import Edges, sum_reducer
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")   # native kernels
    n, key_cap = 1 << 23, 1024
    lane = lambda dt, *width: jax.ShapeDtypeStruct((n,) + width, dt,
                                                   sharding=one_chip)
    combined = Edges(lane(jnp.int32), lane(jnp.int32),
                     {"c": lane(jnp.float32, 1)}, lane(jnp.bool_),
                     lane(jnp.int8))
    keys = jax.ShapeDtypeStruct((key_cap,), jnp.int32, sharding=one_chip)
    compiled = _merge_reduce.lower(sum_reducer(), key_cap, "pallas",
                                   combined, keys).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    flag_gathers = re.findall(rf"= (?:s8|pred)\[{n}\]\S* gather\(", text)
    assert not flag_gathers, flag_gathers
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
