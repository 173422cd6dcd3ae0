"""Pallas TPU kernels: deterministic k-quantile quantize / dequantize.

``quantize``  : weights (G, R, C) + stats -> int8 codes (one VMEM pass;
                int4 packing is a separate cheap pass done by the wrapper).
``dequantize``: int8 codes + stats -> bf16/f32 weights via the *analytic*
                level formula  mu + sigma * Phi^{-1}((c + 1/2)/k)  — no
                codebook, no gather (TPU gathers are slow; erf_inv is a VPU
                polynomial).

Both are elementwise over (G, R, C) tiles with per-channel or per-tensor
statistics, same layout conventions as uniq_noise.py.
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


def _quant_kernel(w_ref, mu_ref, sigma_ref, o_ref, *, k: int):
    w = w_ref[0].astype(jnp.float32)
    mu = mu_ref[0].astype(jnp.float32)
    sigma = sigma_ref[0].astype(jnp.float32)
    z = (w - mu) / sigma
    u = 0.5 * (1.0 + jax.lax.erf(z / _SQRT2))
    u = jnp.clip(u, _EPS, 1.0 - _EPS)
    codes = jnp.clip(jnp.floor(u * k), 0, k - 1)
    if k == 256:  # int8 storage offset
        codes = codes - 128.0
    o_ref[0] = codes.astype(jnp.int8)


def _dequant_kernel(c_ref, mu_ref, sigma_ref, o_ref, *, k: int):
    codes = c_ref[0].astype(jnp.float32)
    if k == 256:  # undo int8 storage offset
        codes = codes + 128.0
    mu = mu_ref[0].astype(jnp.float32)
    sigma = sigma_ref[0].astype(jnp.float32)
    centers = jnp.clip((codes + 0.5) / k, _EPS, 1.0 - _EPS)
    w = mu + sigma * (_SQRT2 * jax.lax.erf_inv(2.0 * centers - 1.0))
    o_ref[0] = w.astype(o_ref.dtype)


def _elementwise_call(kernel, x, mu, sigma, out_dtype, k, block_r, block_c,
                      interpret):
    G, R, C = x.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    if R % block_r or C % block_c:
        raise ValueError(f"({R},{C}) not divisible by ({block_r},{block_c})")
    per_channel = mu.shape[-1] != 1
    stat_c = block_c if per_channel else 1
    stat_map = (lambda g, i, j: (g, 0, j)) if per_channel else \
               (lambda g, i, j: (g, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, k=k),
        grid=(G, R // block_r, C // block_c),
        in_specs=[
            pl.BlockSpec((1, block_r, block_c), lambda g, i, j: (g, i, j)),
            pl.BlockSpec((1, 1, stat_c), stat_map),
            pl.BlockSpec((1, 1, stat_c), stat_map),
        ],
        out_specs=pl.BlockSpec((1, block_r, block_c), lambda g, i, j: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, R, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x, mu, sigma)


@functools.partial(jax.jit, static_argnames=("k", "block_r", "block_c",
                                             "interpret"))
def kquantile_quantize(w, mu, sigma, *, k: int,
                       block_r: int = DEFAULT_BLOCK_R,
                       block_c: int = DEFAULT_BLOCK_C,
                       interpret: bool = False):
    """(G, R, C) weights -> (G, R, C) int8 codes in [0, k)."""
    return _elementwise_call(_quant_kernel, w, mu, sigma, jnp.int8, k,
                             block_r, block_c, interpret)


@functools.partial(jax.jit, static_argnames=("k", "out_dtype", "block_r",
                                             "block_c", "interpret"))
def kquantile_dequantize(codes, mu, sigma, *, k: int,
                         out_dtype=jnp.bfloat16,
                         block_r: int = DEFAULT_BLOCK_R,
                         block_c: int = DEFAULT_BLOCK_C,
                         interpret: bool = False):
    """(G, R, C) int8 codes -> (G, R, C) weights (analytic levels)."""
    return _elementwise_call(_dequant_kernel, codes, mu, sigma, out_dtype, k,
                             block_r, block_c, interpret)
