"""Quantizers (paper Sec. 3.1).

A quantizer is a pair (thresholds T, levels Q); ``quantize`` maps a value to a
bin index (code), ``dequantize`` maps a code back to its representation level.

Implemented:
  * ``kquantile_*``  — the paper's k-quantile (balanced) quantizer: equal
    probability mass per bin, level = bin median.  Via the uniformization
    trick this is a *uniform* quantizer in u-space, so codes are just
    ``floor(k * F(w))`` and levels are ``F^{-1}((i+1/2)/k)``.
  * ``uniform_*``    — uniform quantizer over [-3 sigma, 3 sigma] (paper's
    ablation baseline, Table 3).
  * ``kmeans_*``     — Lloyd-Max l2-optimal quantizer (paper's ablation
    baseline, Table 3), fixed-iteration Lloyd so it jits.

All functions are pure and jit-friendly; codes are int8 (k <= 256) unless the
caller requests otherwise.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.distributions import GaussianModel, EmpiricalModel

Array = jax.Array


# --------------------------------------------------------------------------
# k-quantile quantizer (the paper's contribution)
# --------------------------------------------------------------------------

def kquantile_quantize(w: Array, model, k: int,
                       code_dtype=jnp.int32) -> Array:
    """Codes of the k-quantile quantizer:  c = floor(k * F(w)) in [0, k-1]."""
    u = model.cdf(w)
    # clip in float, then one cast: no int32 copy when code_dtype is uint8
    return jnp.clip(jnp.floor(u * k), 0, k - 1).astype(code_dtype)


def kquantile_dequantize(codes: Array, model, k: int,
                         dtype=jnp.float32) -> Array:
    """Levels of the k-quantile quantizer:  q_c = F^{-1}((c + 1/2)/k).

    Under the Gaussian model this is analytic (mu + sigma * ndtri(.)) — no
    codebook lookup, which is what the fused TPU dequant kernel exploits.
    """
    centers = (codes.astype(jnp.float32) + 0.5) / k
    return model.quantile(centers).astype(dtype)


def kquantile_fakequant(w: Array, model, k: int) -> Array:
    """Round-trip quantize -> dequantize (inference-time weight values)."""
    return kquantile_dequantize(kquantile_quantize(w, model, k), model, k,
                                dtype=w.dtype)


# --------------------------------------------------------------------------
# Uniform quantizer over [-3 sigma, +3 sigma]  (ablation baseline)
# --------------------------------------------------------------------------

def uniform_thresholds(model: GaussianModel, k: int) -> Tuple[Array, Array]:
    """(thresholds (k-1,...), levels (k,...)) of the uniform quantizer."""
    lo = model.mu - 3.0 * model.sigma
    hi = model.mu + 3.0 * model.sigma
    step = (hi - lo) / k
    i = jnp.arange(1, k, dtype=jnp.float32)
    thr = lo + step * i.reshape((k - 1,) + (1,) * jnp.ndim(model.mu))
    j = jnp.arange(k, dtype=jnp.float32)
    lev = lo + step * (j.reshape((k,) + (1,) * jnp.ndim(model.mu)) + 0.5)
    return thr, lev


def uniform_quantize(w: Array, model: GaussianModel, k: int,
                     code_dtype=jnp.int8) -> Array:
    lo = model.mu - 3.0 * model.sigma
    hi = model.mu + 3.0 * model.sigma
    step = (hi - lo) / k
    c = jnp.floor((w - lo) / step).astype(jnp.int32)
    return jnp.clip(c, 0, k - 1).astype(code_dtype)


def uniform_dequantize(codes: Array, model: GaussianModel, k: int,
                       dtype=jnp.float32) -> Array:
    lo = model.mu - 3.0 * model.sigma
    hi = model.mu + 3.0 * model.sigma
    step = (hi - lo) / k
    return (lo + step * (codes.astype(jnp.float32) + 0.5)).astype(dtype)


def uniform_fakequant(w: Array, model: GaussianModel, k: int) -> Array:
    return uniform_dequantize(uniform_quantize(w, model, k), model, k,
                              dtype=w.dtype)


# --------------------------------------------------------------------------
# k-means (Lloyd-Max) quantizer  (ablation baseline)
# --------------------------------------------------------------------------

def lloyd_max(w: Array, k: int, iters: int = 25) -> Array:
    """Fixed-iteration Lloyd-Max on the flattened tensor; returns levels (k,).

    Initialised from the k-quantile levels (good + deterministic).  Each
    iteration assigns samples to the nearest level and recomputes centroids;
    empty bins keep their previous level.
    """
    flat = jax.lax.stop_gradient(w.reshape(-1).astype(jnp.float32))
    model = GaussianModel.fit(flat)
    centers = (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
    init = model.quantile(centers).reshape(-1)

    def body(levels, _):
        # nearest-level assignment
        d = jnp.abs(flat[:, None] - levels[None, :])  # (n, k)
        assign = jnp.argmin(d, axis=1)
        one_hot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (n, k)
        counts = one_hot.sum(axis=0)
        sums = one_hot.T @ flat
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), levels)
        return jnp.sort(new), None

    levels, _ = jax.lax.scan(body, init, None, length=iters)
    return levels


def levels_quantize(w: Array, levels: Array, code_dtype=jnp.int8) -> Array:
    """Nearest-level codes for an explicit (sorted) codebook ``levels`` (k,)."""
    # Midpoint thresholds between consecutive levels.
    thr = 0.5 * (levels[1:] + levels[:-1])  # (k-1,)
    c = jnp.searchsorted(thr, w.astype(jnp.float32)).astype(jnp.int32)
    return c.astype(code_dtype)


def levels_dequantize(codes: Array, levels: Array, dtype=jnp.float32) -> Array:
    return jnp.take(levels, codes.astype(jnp.int32)).astype(dtype)


def kmeans_fakequant(w: Array, k: int, iters: int = 25) -> Array:
    levels = lloyd_max(w, k, iters)
    return levels_dequantize(levels_quantize(w, levels), levels, dtype=w.dtype)


# --------------------------------------------------------------------------
# Generic dispatch
# --------------------------------------------------------------------------

def fakequant(w: Array, k: int, method: str = "kquantile",
              model=None) -> Array:
    """Deterministic quantize->dequantize with the chosen quantizer."""
    if model is None:
        model = GaussianModel.fit(w)
    if method == "kquantile":
        return kquantile_fakequant(w, model, k)
    if method == "uniform":
        return uniform_fakequant(w, model, k)
    if method == "kmeans":
        return kmeans_fakequant(w, k)
    raise ValueError(f"unknown quantizer: {method!r}")
