"""Unified model API dispatching over architecture families.

    init(rng, cfg)                      -> params
    loss_fn(params, cfg, opts, batch)   -> scalar loss          (train)
    prefill(params, cfg, opts, batch)   -> (logits, cache)      (serve)
    decode(params, cfg, opts, cache, tokens, positions)
                                        -> (logits, cache)      (serve)
    cache_specs(cfg, shape)             -> ShapeDtypeStruct pytree
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import encdec, hybrid, lm
from repro.models.lm import ModelOpts

__all__ = ["ModelOpts", "init", "loss_fn", "prefill", "decode",
           "cache_specs", "init_cache", "quantize_for_serving",
           "build_for_serving",
           "supports_slot_cache", "init_slot_cache", "cache_insert",
           "supports_paged_cache", "init_paged_cache",
           "cache_insert_paged", "prefill_chunk"]


def init(rng: jax.Array, cfg: ArchConfig) -> Any:
    if cfg.family == "audio":
        return encdec.init_params_encdec(rng, cfg)
    if cfg.family == "ssm":
        return hybrid.init_params_mamba(rng, cfg)
    if cfg.family == "hybrid":
        return hybrid.init_params_zamba(rng, cfg)
    return lm.init_params(rng, cfg)


def loss_fn(params, cfg: ArchConfig, opts: ModelOpts, batch,
            uniq_scan=None) -> jax.Array:
    """``uniq_scan=(UniqConfig, (L,) modes, rng)`` applies the UNIQ weight
    transform per layer inside the scan (decoder-only families)."""
    if cfg.family == "audio":
        return encdec.forward_train_encdec(params, cfg, opts, batch)
    if cfg.family == "ssm":
        return hybrid.forward_train_mamba(params, cfg, opts, batch)
    if cfg.family == "hybrid":
        return hybrid.forward_train_zamba(params, cfg, opts, batch)
    return lm.forward_train(params, cfg, opts, batch, uniq_scan=uniq_scan)


def prefill(params, cfg: ArchConfig, opts: ModelOpts, batch,
            last_idx=None):
    """``last_idx`` (B,) selects per-sequence last positions for padded
    batched prefill (decoder-only families; see lm.forward_prefill)."""
    if cfg.family in ("audio", "ssm", "hybrid"):
        if last_idx is not None:
            raise ValueError(
                f"last_idx is unsupported for family {cfg.family}: padded "
                "batched prefill only covers decoder-only KV families")
        if cfg.family == "audio":
            return encdec.forward_prefill_encdec(params, cfg, opts, batch)
        if cfg.family == "ssm":
            return hybrid.prefill_mamba(params, cfg, opts, batch)
        return hybrid.prefill_zamba(params, cfg, opts, batch)
    return lm.forward_prefill(params, cfg, opts, batch, last_idx=last_idx)


def decode(params, cfg: ArchConfig, opts: ModelOpts, cache, tokens,
           positions, block_tables=None):
    """``block_tables`` (B, n_pages) int32 switches the decoder-only
    families to the paged-cache layout (see lm.decode_step)."""
    if block_tables is not None:
        if not supports_paged_cache(cfg):
            raise ValueError(
                f"paged decode unsupported for family {cfg.family}")
        return lm.decode_step(params, cfg, opts, cache, tokens, positions,
                              block_tables=block_tables)
    if cfg.family == "audio":
        return encdec.decode_step_encdec(params, cfg, opts, cache, tokens,
                                         positions)
    if cfg.family == "ssm":
        return hybrid.decode_step_mamba(params, cfg, opts, cache, tokens,
                                        positions)
    if cfg.family == "hybrid":
        return hybrid.decode_step_zamba(params, cfg, opts, cache, tokens,
                                        positions)
    return lm.decode_step(params, cfg, opts, cache, tokens, positions)


def cache_specs(cfg: ArchConfig, shape: ShapeConfig, dtype=jnp.bfloat16):
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "audio":
        return encdec.cache_specs_encdec(cfg, B, S // 2, S // 2, dtype)
    if cfg.family == "ssm":
        return hybrid.cache_specs_mamba(cfg, B, dtype)
    if cfg.family == "hybrid":
        return hybrid.cache_specs_zamba(cfg, B, S, dtype)
    return lm.cache_specs(cfg, B, S, dtype)


def init_cache(cfg: ArchConfig, shape: ShapeConfig, dtype=jnp.bfloat16):
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "audio":
        return encdec.init_cache_encdec(cfg, B, S // 2, S // 2, dtype)
    if cfg.family == "ssm":
        return hybrid.init_cache_mamba(cfg, B, dtype)
    if cfg.family == "hybrid":
        return hybrid.init_cache_zamba(cfg, B, S, dtype)
    return lm.init_cache(cfg, B, S, dtype)


# --------------------------------------------------------------------------
# Slot-based serving cache (continuous batching; DESIGN.md Sec. 6)
# --------------------------------------------------------------------------

def supports_slot_cache(cfg: ArchConfig) -> bool:
    """Slot-cache serving covers the families whose cache is the plain
    (L, B, S, KV, hd) KV layout written positionally by lm.decode_step.
    SSM/hybrid state caches and the audio enc-dec cache need a different
    insert rule and are served by the legacy batched path instead."""
    return cfg.family in ("dense", "moe")


def init_slot_cache(cfg: ArchConfig, max_slots: int, max_len: int,
                    dtype=jnp.bfloat16):
    """Zeroed slot cache: one fixed (max_len) KV region per decode slot."""
    if not supports_slot_cache(cfg):
        raise ValueError(f"slot cache unsupported for family {cfg.family}")
    return lm.init_cache(cfg, max_slots, max_len, dtype)


def cache_insert(cache, prefill_cache, slots):
    """Scatter a prefill KV block into decode slots.

    cache          : {"k","v"} (L, max_slots, max_len, KV, hd)
    prefill_cache  : {"k","v"} (L, G, S_pad, KV, hd) from a (padded) batched
                     prefill of G admitted prompts
    slots          : (G,) int32 destination slot ids

    Rows past a prompt's true length hold right-padding garbage, but they
    are never attended: decode at position t masks keys to k_pos <= t and
    overwrites row t before attending, so every visible row has been
    written by either the prompt prefix or an earlier decode step.
    """
    s_pad = prefill_cache["k"].shape[2]
    slots = jnp.asarray(slots, jnp.int32)
    with jax.named_scope("kv_write"):
        return {
            "k": cache["k"].at[:, slots, :s_pad].set(
                prefill_cache["k"].astype(cache["k"].dtype)),
            "v": cache["v"].at[:, slots, :s_pad].set(
                prefill_cache["v"].astype(cache["v"].dtype)),
        }


def supports_paged_cache(cfg: ArchConfig) -> bool:
    """Paged-cache serving covers the same plain-KV families as the slot
    cache; the page pool only changes *where* a position's row lives."""
    return supports_slot_cache(cfg)


def init_paged_cache(cfg: ArchConfig, total_pages: int, page_size: int,
                     dtype=jnp.bfloat16, kv_bits: int = 16):
    """Zeroed paged KV pool; dense (L, total_pages, page_size, KV, hd) at
    kv_bits=16 or the k-quantile codes+stats layout at 8/4 (page 0 is the
    reserved sink; see lm.init_paged_cache and models/kv_cache.py)."""
    if not supports_paged_cache(cfg):
        raise ValueError(f"paged cache unsupported for family {cfg.family}")
    return lm.init_paged_cache(cfg, total_pages, page_size, dtype,
                               kv_bits=kv_bits)


def cache_insert_paged(cache, prefill_cache, page_tables):
    """Scatter a batched-prefill KV block into pool pages (dense or
    quantized layout; see lm.cache_insert_paged)."""
    return lm.cache_insert_paged(cache, prefill_cache, page_tables)


def prefill_chunk(params, cfg: ArchConfig, opts: ModelOpts, cache, tokens,
                  positions, write_pages, write_rows, block_tables,
                  last_idx):
    """Run one sequence's next chunk of prompt tokens against (and into)
    the paged pool — the chunked-prefill step behind prefix caching and
    TTFT smoothing (see lm.prefill_chunk)."""
    if not supports_paged_cache(cfg):
        raise ValueError(
            f"chunked prefill unsupported for family {cfg.family}")
    return lm.prefill_chunk(params, cfg, opts, cache, tokens, positions,
                            write_pages, write_rows, block_tables, last_idx)


def quantize_for_serving(params, bits: int, dist: str = "gaussian"):
    """k-quantile-code all matmul weights of a trained tree for the
    serving path (UNIQ)."""
    return lm.quantize_params_for_serving(params, bits, dist=dist)


def build_for_serving(rng: jax.Array, cfg: ArchConfig, bits: int,
                      dist: str = "gaussian"):
    """``quantize_for_serving(init(rng, cfg), bits)``'s weights, built in
    serving form on the device layer by layer, with dense matmul weights
    in bf16 (decoder-only families; see lm.build_serving_params)."""
    if cfg.family in ("audio", "ssm", "hybrid"):
        raise ValueError(f"no streamed serving build for family "
                         f"{cfg.family}; use init + quantize_for_serving")
    return lm.build_serving_params(rng, cfg, bits, dist=dist)
