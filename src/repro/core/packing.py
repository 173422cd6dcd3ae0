"""Bit-packing of quantization codes for storage/serving.

int4 codes (k <= 16) are packed two-per-byte along the last axis: low nibble
holds the even element, high nibble the odd element.  The last axis must be
even (all our weight matrices have multiple-of-128 trailing dims).

int8 codes (k <= 256) are stored as-is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def pack_int4(codes: Array) -> Array:
    """(..., 2n) int codes in [0,16) -> (..., n) uint8 packed.

    Codes are masked to their low nibble: without the mask, bit 4 of an
    out-of-range even element would bleed into its odd neighbor's nibble
    and silently corrupt it.
    """
    if codes.shape[-1] % 2 != 0:
        raise ValueError(f"last dim must be even, got {codes.shape}")
    c = codes.astype(jnp.uint8) & 0x0F
    lo = c[..., 0::2]
    hi = c[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: Array) -> Array:
    """(..., n) uint8 packed -> (..., 2n) int8 codes in [0,16)."""
    lo = (packed & 0x0F).astype(jnp.int8)
    hi = ((packed >> 4) & 0x0F).astype(jnp.int8)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def store_codes(codes: Array, bits: int) -> Array:
    """uint8 codes in [0, 2**bits) -> serving storage: int4 packed two per
    byte (uint8), or int8 offset by -128 (the k=256 storage convention)."""
    if bits == 4:
        return pack_int4(codes)
    if bits == 8:
        # c - 128 in two's complement is c with its top bit flipped
        return jax.lax.bitcast_convert_type(codes.astype(jnp.uint8) ^ 0x80,
                                            jnp.int8)
    raise ValueError(f"serving bits must be 4 or 8, got {bits}")


def packed_shape(shape, bits: int):
    """Storage shape for codes of ``shape`` at ``bits`` in {4, 8}."""
    if bits == 4:
        return (*shape[:-1], shape[-1] // 2)
    if bits == 8:
        return tuple(shape)
    raise ValueError(f"unsupported storage bits: {bits}")


def storage_dtype(bits: int):
    if bits in (4, 8):
        return jnp.uint8 if bits == 4 else jnp.int8
    raise ValueError(f"unsupported storage bits: {bits}")
