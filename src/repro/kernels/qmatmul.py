"""Pallas TPU kernel: dequant-fused matmul over k-quantile-coded weights.

The serving hot-spot.  Weights live in HBM as packed int4 (two codes/byte)
or int8 k-quantile codes with per-out-channel Gaussian statistics; each
(bk, bn) weight tile is unpacked and dequantized *in VMEM* via the analytic
level formula

    w = mu_n + sigma_n * Phi^{-1}((c + 1/2) / k)        (erf_inv polynomial)

and fed to the MXU against (bm, bk) activation tiles, f32 accumulation
across the K grid dimension.  HBM weight traffic drops 4x (W4) vs bf16 —
decode-time matmuls are memory-bound, so this is the paper's BOPs win
translated to the TPU memory hierarchy (DESIGN.md Sec. 2).

Batch-persistent schedule (the uniqfast restructure): the grid is ordered
``(N//bn, K//bk, M//bm)`` with the M axis innermost, and the dequantized
(bk, bn) weight tile lives in a VMEM scratch buffer keyed by the (K, N)
grid position — it is unpacked + dequantized once, when the first M tile
arrives (``@pl.when(i == 0)``), and every subsequent M tile reuses it.
Each weight tile therefore pays the erf_inv polynomial (or LUT selects)
once per *call* instead of once per (m, k, n) tile-visit; the old
schedule re-dequantized the same tile M//bm times.

Because the M-innermost order makes output revisits across the K axis
non-consecutive (a TPU pipelining hazard: an output block flushed between
revisits would lose its accumulator), the kernel writes *revisit-free
per-K-split partials* — out_shape ``(K//bk, M, N)``, each grid point
writing its (1, bm, bn) block exactly once — and the wrapper sums the
K-split axis in a cheap f32 epilogue.  Decode (K//bk small) pays a few
extra output rows; prefill trades that for the M//bm-fold dequant saving.

Block shapes are a tuned config axis (``TUNED_BLOCKS`` /
``default_blocks``) instead of one hard-coded triple: decode shapes
(M <= 32 rows) want wide N tiles so the persistent scratch amortizes over
more columns, prefill wants the classic MXU-square tiles.  Non-divisible
M/K/N are zero-padded to the block grid (padded K rows of the activation
are zero, so garbage dequant levels in the padded weight region contribute
exact zeros; padded M/N are sliced off).

VMEM budget at the prefill config (256, 512, 256), W4: a-tile 512 KB,
packed w-tile 64 KB, out partial 256 KB (x2 double-buffered) + persistent
dequant scratch 512 KB  ->  ~2.2 MB of the 16 MiB/core budget
(``analysis/kernel_audit.py`` pins this estimate in CI).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SQRT2 = 1.4142135623730951
_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One point on the (bm, bk, bn) block-shape tuning axis."""
    bm: int
    bk: int
    bn: int


# The tuned table.  "decode" favours wide bn so the persistent dequant
# scratch is reused across more output columns per unpack; "prefill" is
# the classic MXU-square tiling; "lut" keeps bk modest because the k
# select passes scale with the tile area.
TUNED_BLOCKS = {
    "prefill": BlockConfig(bm=256, bk=512, bn=256),
    "decode": BlockConfig(bm=32, bk=512, bn=512),
    "lut": BlockConfig(bm=256, bk=256, bn=256),
}

# Back-compat aliases (audit/bench code refers to the classic defaults).
DEFAULT_BM = TUNED_BLOCKS["prefill"].bm
DEFAULT_BK = TUNED_BLOCKS["prefill"].bk
DEFAULT_BN = TUNED_BLOCKS["prefill"].bn

_DECODE_M_MAX = 32


def default_blocks(M: int, variant: str = "gaussian") -> BlockConfig:
    """Pick the tuned block config for a call shape (M rows, kernel kind)."""
    if variant == "lut":
        return TUNED_BLOCKS["lut"]
    return TUNED_BLOCKS["decode"] if M <= _DECODE_M_MAX \
        else TUNED_BLOCKS["prefill"]


def _resolve_blocks(M: int, K: int, N: int, bits: int, variant: str,
                    bm: Optional[int], bk: Optional[int],
                    bn: Optional[int]):
    cfg = default_blocks(M, variant)
    bm = min(bm if bm is not None else cfg.bm, M)
    bk = min(bk if bk is not None else cfg.bk, K)
    bn = min(bn if bn is not None else cfg.bn, N)
    if bits == 4 and bn % 2:
        raise ValueError(f"bn must be even for packed int4, got {bn}")
    return bm, bk, bn


def _pad_operands(a, w_packed, mu_sigma_or_lut, bits: int,
                  M: int, K: int, N: int, bm: int, bk: int, bn: int):
    """Zero-pad operands to the block grid.

    Padded K rows of ``a`` are zero, so whatever the padded weight region
    dequantizes to contributes exactly zero; padded M rows / N columns are
    sliced off by the caller.  Returns padded operands + padded dims.
    """
    mpad, kpad, npad = (-M) % bm, (-K) % bk, (-N) % bn
    if mpad or kpad:
        a = jnp.pad(a, ((0, mpad), (0, kpad)))
    if kpad or npad:
        wpad = npad // 2 if bits == 4 else npad
        w_packed = jnp.pad(w_packed, ((0, kpad), (0, wpad)))
    padded_stats = []
    for arr in mu_sigma_or_lut:
        if npad:
            arr = jnp.pad(arr, ((0, 0), (0, npad)))
        padded_stats.append(arr)
    return a, w_packed, padded_stats, M + mpad, K + kpad, N + npad


def _unpack_dequant(w_blk, mu, sigma, bits: int, k: int, compute_dtype):
    """(bk, bn//2) packed uint8 or (bk, bn) int8 -> (bk, bn) dequantized."""
    if bits == 4:
        lo = (w_blk & 0x0F).astype(jnp.float32)
        hi = ((w_blk >> 4) & 0x0F).astype(jnp.float32)
        codes = jnp.stack([lo, hi], axis=-1)          # (bk, bn//2, 2)
        codes = codes.reshape(w_blk.shape[0], w_blk.shape[1] * 2)
    else:
        codes = w_blk.astype(jnp.float32)
        if k == 256:  # undo int8 storage offset
            codes = codes + 128.0
    centers = jnp.clip((codes + 0.5) / k, _EPS, 1.0 - _EPS)
    w = mu + sigma * (_SQRT2 * jax.lax.erf_inv(2.0 * centers - 1.0))
    return w.astype(compute_dtype)


def _kernel(a_ref, w_ref, mu_ref, sigma_ref, o_ref, w_scr, *, bits: int,
            k: int):
    i = pl.program_id(2)          # M axis, innermost

    @pl.when(i == 0)
    def _dequant():               # once per (K, N) tile; all M tiles reuse
        w_scr[...] = _unpack_dequant(
            w_ref[...], mu_ref[...].astype(jnp.float32),
            sigma_ref[...].astype(jnp.float32), bits, k, w_scr.dtype)

    o_ref[0] = jnp.dot(a_ref[...], w_scr[...],
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "out_dtype", "bm", "bk",
                                             "bn", "interpret"))
def qmatmul(a: jax.Array, w_packed: jax.Array, mu: jax.Array,
            sigma: jax.Array, *, bits: int, out_dtype=jnp.float32,
            bm: Optional[int] = None, bk: Optional[int] = None,
            bn: Optional[int] = None, interpret: bool = False) -> jax.Array:
    """a (M, K) @ dequant(w_packed) (K, N) -> (M, N).

    w_packed : (K, N//2) uint8 if bits==4 else (K, N) int8.
    mu/sigma : (1, N) f32 per-out-channel statistics.
    bm/bk/bn : block shapes; None picks from the tuned table by call shape.
    """
    M, K = a.shape
    N = w_packed.shape[1] * 2 if bits == 4 else w_packed.shape[1]
    if w_packed.shape[0] != K:
        raise ValueError(f"K mismatch: a {a.shape} vs w {w_packed.shape}")
    bm, bk, bn = _resolve_blocks(M, K, N, bits, "gaussian", bm, bk, bn)
    a, w_packed, (mu, sigma), Mp, Kp, Np = _pad_operands(
        a, w_packed, (mu, sigma), bits, M, K, N, bm, bk, bn)
    wn_blk = bn // 2 if bits == 4 else bn
    ksplit = Kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, k=2 ** bits),
        grid=(Np // bn, ksplit, Mp // bm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
            pl.BlockSpec((bk, wn_blk), lambda j, kk, i: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, kk, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda j, kk, i: (kk, i, j)),
        out_shape=jax.ShapeDtypeStruct((ksplit, Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bn), a.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(a, w_packed, mu, sigma)
    return out.sum(axis=0)[:M, :N].astype(out_dtype)


def _kernel_lut(a_ref, w_ref, lut_ref, o_ref, w_scr, *, bits: int, k: int):
    i = pl.program_id(2)          # M axis, innermost

    @pl.when(i == 0)
    def _dequant():               # k select passes once per (K, N) tile
        w_blk = w_ref[...]
        if bits == 4:
            lo = (w_blk & 0x0F).astype(jnp.int32)
            hi = ((w_blk >> 4) & 0x0F).astype(jnp.int32)
            codes = jnp.stack([lo, hi], axis=-1)
            codes = codes.reshape(w_blk.shape[0], w_blk.shape[1] * 2)
        else:
            codes = w_blk.astype(jnp.int32)
            if k == 256:  # undo int8 storage offset
                codes = codes + 128

        # Per-channel codebook gather, k select passes over the (bk, bn)
        # tile: w[r, c] = lut[codes[r, c], c].  Avoids a (bk, bn, k)
        # one-hot intermediate (32 MB of VMEM at k=256 for the default
        # tiles); the VPU select is cheap relative to the MXU tiles it
        # now feeds M//bm times over.
        def pick(j, w):
            row = lut_ref[pl.dslice(j, 1), :].astype(jnp.float32)  # (1, bn)
            return jnp.where(codes == j, row, w)

        w_scr[...] = jax.lax.fori_loop(0, k, pick,
                                       jnp.zeros(codes.shape, jnp.float32))

    o_ref[0] = jnp.dot(a_ref[...].astype(jnp.float32), w_scr[...],
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "out_dtype", "bm", "bk",
                                             "bn", "interpret"))
def qmatmul_lut(a: jax.Array, w_packed: jax.Array, lut: jax.Array, *,
                bits: int, out_dtype=jnp.float32, bm: Optional[int] = None,
                bk: Optional[int] = None, bn: Optional[int] = None,
                interpret: bool = False) -> jax.Array:
    """a (M, K) @ lut-dequant(w_packed) (K, N) -> (M, N).

    The codebook variant of :func:`qmatmul` for codes whose levels have
    *no* analytic form — ``dist="empirical"`` checkpoints, whose k levels
    are order statistics of the weight population (the paper's "look-up
    table availability" assumption).  ``lut`` is a (k, N) f32 table of
    per-out-channel levels; per-tensor codebooks broadcast to (k, N)
    before the call (``EmpiricalModel.level_values``).

    w_packed : (K, N//2) uint8 if bits==4 else (K, N) int8 (k=256 offset).
    """
    M, K = a.shape
    N = w_packed.shape[1] * 2 if bits == 4 else w_packed.shape[1]
    k = 2 ** bits
    if w_packed.shape[0] != K:
        raise ValueError(f"K mismatch: a {a.shape} vs w {w_packed.shape}")
    if lut.shape != (k, N):
        raise ValueError(f"lut must be ({k}, {N}), got {lut.shape}")
    bm, bk, bn = _resolve_blocks(M, K, N, bits, "lut", bm, bk, bn)
    a, w_packed, (lut,), Mp, Kp, Np = _pad_operands(
        a, w_packed, (lut,), bits, M, K, N, bm, bk, bn)
    wn_blk = bn // 2 if bits == 4 else bn
    ksplit = Kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel_lut, bits=bits, k=k),
        grid=(Np // bn, ksplit, Mp // bm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
            pl.BlockSpec((bk, wn_blk), lambda j, kk, i: (kk, j)),
            pl.BlockSpec((k, bn), lambda j, kk, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda j, kk, i: (kk, i, j)),
        out_shape=jax.ShapeDtypeStruct((ksplit, Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(a, w_packed, lut)
    return out.sum(axis=0)[:M, :N].astype(out_dtype)


def _kernel_a8(scale_ref, a_ref, w_ref, mu_ref, sigma_ref, o_ref, w_scr, *,
               bits: int, k: int):
    i = pl.program_id(2)          # M axis, innermost

    @pl.when(i == 0)
    def _dequant():               # once per (K, N) tile; all M tiles reuse
        w_scr[...] = _unpack_dequant(
            w_ref[...], mu_ref[...].astype(jnp.float32),
            sigma_ref[...].astype(jnp.float32), bits, k, jnp.bfloat16)

    a = a_ref[...].astype(jnp.float32) * scale_ref[0]
    o_ref[0] = jnp.dot(a.astype(jnp.bfloat16), w_scr[...],
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "out_dtype", "bm", "bk",
                                             "bn", "interpret"))
def qmatmul_a8(a_codes: jax.Array, a_scale: jax.Array, w_packed: jax.Array,
               mu: jax.Array, sigma: jax.Array, *, bits: int,
               out_dtype=jnp.float32, bm: Optional[int] = None,
               bk: Optional[int] = None, bn: Optional[int] = None,
               interpret: bool = False) -> jax.Array:
    """W4/W8 x A8: int8 activations (per-tensor scale) against coded weights."""
    M, K = a_codes.shape
    N = w_packed.shape[1] * 2 if bits == 4 else w_packed.shape[1]
    if w_packed.shape[0] != K:
        raise ValueError(f"K mismatch: a {a_codes.shape} vs w "
                         f"{w_packed.shape}")
    bm, bk, bn = _resolve_blocks(M, K, N, bits, "gaussian", bm, bk, bn)
    a_codes, w_packed, (mu, sigma), Mp, Kp, Np = _pad_operands(
        a_codes, w_packed, (mu, sigma), bits, M, K, N, bm, bk, bn)
    wn_blk = bn // 2 if bits == 4 else bn
    ksplit = Kp // bk
    a_scale = jnp.asarray(a_scale, jnp.float32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_kernel_a8, bits=bits, k=2 ** bits),
        grid=(Np // bn, ksplit, Mp // bm),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
            pl.BlockSpec((bk, wn_blk), lambda j, kk, i: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, kk, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda j, kk, i: (kk, i, j)),
        out_shape=jax.ShapeDtypeStruct((ksplit, Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(a_scale, a_codes, w_packed, mu, sigma)
    return out.sum(axis=0)[:M, :N].astype(out_dtype)
