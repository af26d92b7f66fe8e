"""The decode-attention kernel compiled by the real TPU compiler at the
benchmark cell's shape (gpt2-medium, 96 rows x max_len 1024), without a
chip: Mosaic's refusals (block shapes, scoped VMEM, scalar-prefetch index
maps) show up here, numerics and times do not. The topology is described
inside a fixture, never at import (only one process may load libtpu, and
xdist workers all import this file); keep such tests in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from rlo_tpu.pallas.decode import flash_block_decode

B, NH, D, L = 96, 16, 64, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("T,cache_dtype", [(1, jnp.bfloat16),
                                           (4, jnp.bfloat16),
                                           (1, jnp.int8)])
def test_flash_decode_compiles_for_v5e(one_chip, T, cache_dtype):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [shape((B, T, NH, D), jnp.bfloat16),
            shape((B, NH, D, L), cache_dtype),
            shape((B, NH, D, L), cache_dtype), shape((B,), jnp.int32)]
    if cache_dtype == jnp.int8:
        args += [shape((B, NH, L), jnp.float32)] * 2

    def attend(q, k, v, pos, *scales):
        return flash_block_decode(q, k, v, pos, 0.125, *scales,
                                  interpret=False)

    text = jax.jit(attend).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("flash_decode" if T == 1 else "flash_block_decode") in text
