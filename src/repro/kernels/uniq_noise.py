"""Pallas TPU kernel: fused UNIQ noise-injection / freeze transform.

One VMEM pass computes, per weight tile,

    u        = Phi((w - mu) / sigma)
    u_noise  = clip(u + (e01 - 0.5)/k)             # NOISE mode
    u_frozen = (floor(u * k) + 0.5) / k            # FROZEN mode
    w_hat    = mu + sigma * Phi^{-1}(select(mode))
    out      = select(mode == CLEAN, w, w_hat)

replacing three separate HBM round-trips (uniformize / perturb /
deuniformize) of the naive formulation.

Two noise sources:
  * ``host``   (default): e01 ~ U[0,1) is an input operand generated with
    ``jax.random`` — bit-exact against the jnp reference, validated in
    interpret mode on CPU.
  * ``onchip``: e01 is drawn inside the kernel with the TPU hardware PRNG
    (`pltpu.prng_random_bits`), eliminating the (G, R, C) f32 noise read
    from HBM (1/3 of the kernel's input traffic).  TPU-only: the Pallas
    interpreter stubs `prng_random_bits` to zeros (jax 0.8.2), so this path
    is *not* CPU-validatable; it shares every other instruction with the
    host-noise path, which is.

Layout: weights are grouped ``(G, R, C)`` (G = scan-stacked layers, G=1 for
plain tensors); statistics ``(G, 1, C)`` or ``(G, 1, 1)``; per-group mode
``(G,)`` int32 in SMEM.  Grid = (G, R/br, C/bc), all-parallel.

The MXU is untouched — this is a pure VPU kernel; default blocks (256, 512)
keep ~2.5 MB/tile in VMEM (w + e01 + out f32 + temps), well under the
16 MB/core budget, trailing dim a multiple of the 128-lane width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SQRT2 = 1.4142135623730951
_EPS = 1e-6

DEFAULT_BLOCK_R = 256
DEFAULT_BLOCK_C = 512

CLEAN, NOISE, FROZEN = 0, 1, 2


def _body(w, mu, sigma, e01, mode, k):
    z = (w - mu) / sigma
    u = 0.5 * (1.0 + jax.lax.erf(z / _SQRT2))
    u = jnp.clip(u, _EPS, 1.0 - _EPS)
    u_noise = jnp.clip(u + (e01 - 0.5) / k, _EPS, 1.0 - _EPS)
    codes = jnp.clip(jnp.floor(u * k), 0, k - 1)
    u_frozen = (codes + 0.5) / k
    u_sel = jnp.where(mode == NOISE, u_noise, u_frozen)
    w_hat = mu + sigma * (_SQRT2 * jax.lax.erf_inv(2.0 * u_sel - 1.0))
    return jnp.where(mode == CLEAN, w, w_hat)


def _kernel_host(mode_ref, w_ref, mu_ref, sigma_ref, e_ref, o_ref, *, k: int):
    g = pl.program_id(0)
    w = w_ref[0].astype(jnp.float32)
    out = _body(w, mu_ref[0].astype(jnp.float32),
                sigma_ref[0].astype(jnp.float32), e_ref[0], mode_ref[g], k)
    o_ref[0] = out.astype(o_ref.dtype)


def _kernel_onchip(seed_ref, mode_ref, w_ref, mu_ref, sigma_ref, o_ref, *,
                   k: int):
    g = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    # Hash-combine grid coords into the seed so tiles draw independent
    # streams regardless of grid scheduling.
    s = (seed_ref[0]
         + g * jnp.int32(1000003)
         + i * jnp.int32(7919)
         + j * jnp.int32(104729))
    pltpu.prng_seed(s)
    bits = pltpu.prng_random_bits(w_ref[0].shape).astype(jnp.uint32)
    e01 = (bits >> 8).astype(jnp.float32) * (2.0 ** -24)
    w = w_ref[0].astype(jnp.float32)
    out = _body(w, mu_ref[0].astype(jnp.float32),
                sigma_ref[0].astype(jnp.float32), e01, mode_ref[g], k)
    o_ref[0] = out.astype(o_ref.dtype)


def _specs(G, R, C, block_r, block_c, mu):
    per_channel = mu.shape[-1] != 1
    stat_c = block_c if per_channel else 1
    stat_map = (lambda g, i, j: (g, 0, j)) if per_channel else \
               (lambda g, i, j: (g, 0, 0))
    data = pl.BlockSpec((1, block_r, block_c), lambda g, i, j: (g, i, j))
    stat = pl.BlockSpec((1, 1, stat_c), stat_map)
    return data, stat


@functools.partial(jax.jit, static_argnames=("k", "block_r", "block_c",
                                             "interpret"))
def uniq_noise_fwd(w: jax.Array, mu: jax.Array, sigma: jax.Array,
                   mode: jax.Array, e01: jax.Array, *, k: int,
                   block_r: int = DEFAULT_BLOCK_R,
                   block_c: int = DEFAULT_BLOCK_C,
                   interpret: bool = False) -> jax.Array:
    """Host-noise fused transform (validated path).

    w : (G, R, C);  mu, sigma : (G, 1, C) or (G, 1, 1);
    mode : (G,) int32;  e01 : (G, R, C) f32 in [0, 1).
    """
    G, R, C = w.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    if R % block_r or C % block_c:
        raise ValueError(f"({R},{C}) not divisible by ({block_r},{block_c})")
    data, stat = _specs(G, R, C, block_r, block_c, mu)
    mode = jnp.asarray(mode, jnp.int32).reshape((G,))
    return pl.pallas_call(
        functools.partial(_kernel_host, k=k),
        grid=(G, R // block_r, C // block_c),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), data, stat, stat,
                  data],
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct((G, R, C), w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(mode, w, mu, sigma, e01)


@functools.partial(jax.jit, static_argnames=("k", "block_r", "block_c",
                                             "interpret"))
def uniq_noise_fwd_onchip(w: jax.Array, mu: jax.Array, sigma: jax.Array,
                          mode: jax.Array, seed: jax.Array, *, k: int,
                          block_r: int = DEFAULT_BLOCK_R,
                          block_c: int = DEFAULT_BLOCK_C,
                          interpret: bool = False) -> jax.Array:
    """On-chip-PRNG variant (TPU hardware only; see module docstring)."""
    G, R, C = w.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    if R % block_r or C % block_c:
        raise ValueError(f"({R},{C}) not divisible by ({block_r},{block_c})")
    data, stat = _specs(G, R, C, block_r, block_c, mu)
    mode = jnp.asarray(mode, jnp.int32).reshape((G,))
    seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    return pl.pallas_call(
        functools.partial(_kernel_onchip, k=k),
        grid=(G, R // block_r, C // block_c),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM), data, stat, stat],
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct((G, R, C), w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, mode, w, mu, sigma)
