"""Ahead-of-time compiles of the served Pallas kernel for a described TPU.

The TPU compiler is installed with JAX and compiles for a v5e that is
described, not attached, so these tests catch what interpret mode cannot:
casts Mosaic does not lower, tiles it refuses, and kernels that need more
scoped VMEM than the chip allows.  Shapes are granite_3_8b's decode at
batch 8 (KV 8, G 4, head_dim 128, page 64, 16 pages, 4 splits).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attn


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_paged_quant_attention_compiles_for_v5e(one_chip, kv_bits):
    B, KV, G, D, page, n_pages, splits = 8, 8, 4, 128, 64, 16, 4
    P = B * n_pages + 1
    Dc = D // 2 if kv_bits == 4 else D
    code_dtype = jnp.uint8 if kv_bits == 4 else jnp.int8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    codes = spec((P, page, KV, Dc), code_dtype)
    stat = spec((P, page, KV), jnp.bfloat16)
    fn = jax.jit(lambda *a: paged_attn.paged_quant_attention(
        *a, kv_bits=kv_bits, splits=splits))
    compiled = fn.lower(spec((B, 1, KV * G, D), jnp.bfloat16), codes, stat,
                        stat, codes, stat, stat,
                        spec((B, n_pages), jnp.int32),
                        spec((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
