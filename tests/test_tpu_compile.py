"""Ahead-of-time compiles of the served programs for a described TPU.

The TPU compiler is installed with JAX and compiles for a v5e that is
described, not attached, so these tests catch what interpret mode cannot:
casts Mosaic does not lower, tiles it refuses, and kernels that need more
scoped VMEM than the chip allows.  The Pallas kernel's shapes are
granite_3_8b's decode at batch 8 (KV 8, G 4, head_dim 128, page 64, 16
pages, 4 splits).  The engine's decode and chunk programs are compiled
with Granite's head geometry on a narrow model, to see how the chip's
compiler lays out their KV pool.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import base as cb
from repro.kernels import paged_attn
from repro.launch.serve import SERVE_OPTS
from repro.models import model
from repro.serve.engine import Engine, EngineConfig


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


@pytest.mark.parametrize("kv_bits", [16, 8])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_step_program_updates_pool_in_place(one_chip, program, kv_bits):
    """The engine's decode and chunk programs update the donated pool where
    it lies: the pool is aliased to the output, and the compiled program
    holds no pool-sized temporary (a restacked or copied pool).

    Granite's head geometry (KV 8, head_dim 128, page 64) at 3 layers and a
    150 MB kv16 pool: below about 100 MB the chip's compiler updated even a
    restacked pool in place, so a smaller pool would not show the copy."""
    cfg = dataclasses.replace(cb.get_smoke("granite_3_8b"), n_layers=3,
                              n_heads=32, n_kv_heads=8, head_dim=128)
    M, page, max_len = 4, 64, 256
    eng = Engine(model.init(jax.random.PRNGKey(0), cfg), cfg, SERVE_OPTS,
                 EngineConfig(max_slots=M, max_len=max_len, page_size=page,
                              total_pages=193, kv_bits=kv_bits,
                              prefix_cache=True))

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(spec, eng.params)
    pool = jax.tree.map(spec, eng.kv_pool)
    tables = ints(M, max_len // page)
    sampling = (jax.ShapeDtypeStruct((M,), jnp.float32, sharding=one_chip),
                ints(M), ints(M))
    if program == "decode":
        fn, args = eng._decode_step, (params, pool, ints(M), ints(M), tables)
    else:
        rows = ints(M, page)
        fn, args = eng._chunk_step, (params, pool, rows, rows, rows, rows,
                                     tables, ints(M), ints(M))
    mem = fn.lower(*args, *sampling).compile().memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes      # the device's layout
    assert mem.temp_size_in_bytes < pool_bytes, (mem.temp_size_in_bytes,
                                                 pool_bytes)
