"""Pallas TPU kernel: fused gather+unpack+dequant paged decode attention.

The serving-side counterpart of the qmatmul kernel (DESIGN.md Sec. 2): at
decode time the KV pool — not the weights — is the HBM roofline term, and
with k-quantile-coded pages (models/kv_cache.py) the pool bytes drop ~2x
(kv8) / ~3.6x (kv4).  This kernel keeps the win by never materializing a
dense pool: per grid step the *scalar-prefetched* block table drives the
BlockSpec index map, so only the pages a sequence actually owns are DMA'd
HBM->VMEM, as packed codes; unpack (mask/shift for int4) and the analytic
dequant

    x = mu_rh + sigma_rh * Phi^{-1}((c + 1/2) / k)        (erf_inv)

run on the VPU against the page tile — the int4 nibble unpack for both K
and V pages is issued *before* either MXU dot, so the VPU unpack of the
next operand overlaps the MXU's current dot — and an online softmax
accumulates across the page axis in VMEM scratch.  Per-(row, head)
statistics ride in the same page geometry as the codes, so one index map
serves all six operands.

Split-K schedule (the uniqfast restructure): each sequence's pages are
partitioned across a *parallel* ``splits`` grid axis — grid
``(B, splits, pages_per_split)`` — so long-context decode no longer
serializes over the whole page list.  Each split runs the same online
softmax over its page range and emits flash-decoding partials
``(m, l, acc)`` per (batch, split); a cheap jnp combine epilogue rescales
by ``alpha_s = exp(m_s - max_s m_s)`` and merges:

    l = sum_s alpha_s l_s,   acc = sum_s alpha_s acc_s,   out = acc / l.

Splits that see only masked rows carry ``m = -inf, l = 0`` and combine to
exact zeros.  ``splits`` is a tuned static axis (default: 1 below 8
pages, else 4); the block table is sink-padded to ``splits *
pages_per_split`` and padded entries are masked by the causal bound.

Interpret mode executes the same body on CPU (tier-1 parity tests vs the
jnp reference in ``models/attention.py``); compiled Mosaic needs TPU-
friendly dims (page a multiple of the sublane tile, D a multiple of 128)
— real configs (page 64, hd 128) satisfy this, smoke shapes run
interpreted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SQRT2 = 1.4142135623730951
_EPS = 1e-6
NEG_INF = -1e30

# below this many pages a single split saturates the page axis; above,
# flash-decoding's canonical 4-way split covers serving contexts
_SPLIT_MIN_PAGES = 8
DEFAULT_SPLITS = 4


def default_splits(n_pages: int) -> int:
    """Tuned split count for a table width (the split-K config axis)."""
    if n_pages < _SPLIT_MIN_PAGES:
        return 1
    return min(DEFAULT_SPLITS, n_pages)


def _dequant_parts(codes, mu, sigma, bits: int, k: int):
    """(page, KV, Dc) codes + (page, KV) stats -> tuple of (page, KV, Dc)
    f32 planes: one plane at kv8, the even- and odd-column planes at kv4.

    The int4 nibbles are unpacked through int32 (Mosaic lowers no
    uint8 -> f32 cast) and never interleaved back to (page, KV, D): an
    interleave builds a trailing axis of 2, which the TPU tiles to 128
    lanes — 64x the plane's VMEM.  ``pack_int4`` puts even columns in the
    low nibble, so plane 0 holds columns 0, 2, 4, ... and plane 1 the odd
    ones; the caller splits q and the V accumulator the same way.
    """
    c = codes.astype(jnp.int32)
    if bits == 4:
        planes = (c & 0x0F, (c >> 4) & 0x0F)
    else:
        planes = (c + 128 if k == 256 else c,)  # undo int8 storage offset
    mu = mu.astype(jnp.float32)[..., None]
    sigma = sigma.astype(jnp.float32)[..., None]
    out = []
    for p in planes:
        centers = jnp.clip((p.astype(jnp.float32) + 0.5) / k, _EPS,
                           1.0 - _EPS)
        out.append(mu + sigma * (_SQRT2 * jax.lax.erf_inv(2.0 * centers
                                                          - 1.0)))
    return tuple(out)


def _kernel(bt_ref, qpos_ref, win_ref, q_ref, kc_ref, km_ref, ks_ref,
            vc_ref, vm_ref, vs_ref, m_out, l_out, acc_out, m_scr, l_scr,
            acc_scr, *, bits: int, k: int, page: int, pages_per_split: int,
            logit_cap, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # unpack+dequant BOTH pages up front: the VPU nibble unpack of V
    # overlaps the MXU's score dot instead of stalling behind it
    kd = _dequant_parts(kc_ref[0], km_ref[0], ks_ref[0], bits, k)
    vd = _dequant_parts(vc_ref[0], vm_ref[0], vs_ref[0], bits, k)

    # scores: sum over column planes of (KV, G, Dc) x (KV, Dc, page)
    sc = None
    for h, kh in enumerate(kd):
        qh = q_ref[0, h].astype(jnp.float32) * scale       # (KV, G, Dc)
        part = jax.lax.dot_general(
            qh, jnp.transpose(kh, (1, 2, 0)),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        sc = part if sc is None else sc + part
    if logit_cap is not None:
        sc = logit_cap * jnp.tanh(sc / logit_cap)
    j = s * pages_per_split + t                            # logical page
    rows = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = rows <= qpos_ref[b]
    # sliding window (traced per-layer scalar; BIG_WINDOW sentinel = global)
    valid &= (qpos_ref[b] - rows) < win_ref[0]
    sc = jnp.where(valid, sc, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
    alpha = jnp.exp(m_prev - m_new)                        # <= 1, finite
    pexp = jnp.where(valid, jnp.exp(sc - m_new[..., None]), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=-1)
    # per column plane: (KV, G, page) x (KV, page, Dc) -> (KV, G, Dc)
    for h, vh in enumerate(vd):
        acc_scr[h] = acc_scr[h] * alpha[..., None] + jax.lax.dot_general(
            pexp, jnp.transpose(vh, (1, 0, 2)),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(t == pages_per_split - 1)
    def _emit():                 # per-split flash-decoding partials
        m_out[0, 0] = m_scr[...]
        l_out[0, 0] = l_scr[...]
        acc_out[0, 0] = acc_scr[...]


BIG_WINDOW = 1 << 30


@functools.partial(jax.jit, static_argnames=("kv_bits", "logit_cap",
                                             "splits", "interpret"))
def paged_quant_attention(q: jax.Array, k_codes: jax.Array, k_mu: jax.Array,
                          k_sigma: jax.Array, v_codes: jax.Array,
                          v_mu: jax.Array, v_sigma: jax.Array,
                          block_tables: jax.Array, q_pos: jax.Array, *,
                          kv_bits: int, window=None, logit_cap=None,
                          splits: Optional[int] = None,
                          interpret: bool = False) -> jax.Array:
    """q (B, 1, H, D) vs coded pool pages -> (B, 1, H, D).

    k/v_codes : (P, page, KV, D//2) uint8 (kv4) or (P, page, KV, D) int8.
    k/v stats : (P, page, KV) per-(row, head) mu/sigma.
    block_tables (B, n_pages) int32, q_pos (B,) int32; rows past q_pos
    (sink or never-written) are masked exactly as in the dense path.
    ``window``: causal sliding-window width — a *traced* scalar (the
    decode scan's per-layer window, BIG_WINDOW sentinel for global), so
    local and global layers share one compiled kernel.
    ``splits``: split-K parallelism over the page axis; None picks the
    tuned default for the table width.
    """
    B, _, H, D = q.shape
    P, page, KV = k_mu.shape
    G = H // KV
    n_pages = block_tables.shape[1]
    if splits is None:
        splits = default_splits(n_pages)
    splits = max(1, min(splits, n_pages))
    pages_per_split = -(-n_pages // splits)
    k = 2 ** kv_bits
    Dc = k_codes.shape[-1]
    n_planes = D // Dc               # 2 at kv4: even / odd column planes
    # q in the kernel's planar column order: (B, plane, KV, G, Dc), plane
    # h holding columns h, h + n_planes, ... (pack_int4's nibble order)
    qg = jnp.moveaxis(q.reshape(B, KV, G, Dc, n_planes), -1, 1)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    pad = splits * pages_per_split - n_pages
    if pad:
        # sink-pad the table: padded logical pages sit past every q_pos
        # (q_pos < n_pages * page), so the causal bound masks them out
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if window is None:
        window = BIG_WINDOW
    window = jnp.asarray(window, jnp.int32).reshape((1,))

    def page_map(b, s, t, bt, qp, win):
        return (bt[b, s * pages_per_split + t], 0, 0, 0)

    def stat_map(b, s, t, bt, qp, win):
        return (bt[b, s * pages_per_split + t], 0, 0)

    def q_map(b, s, t, bt, qp, win):
        return (b, 0, 0, 0, 0)

    def part_map(b, s, t, bt, qp, win):
        return (b, s, 0, 0)

    def acc_map(b, s, t, bt, qp, win):
        return (b, s, 0, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, splits, pages_per_split),
        in_specs=[
            pl.BlockSpec((1, n_planes, KV, G, Dc), q_map),
            pl.BlockSpec((1, page, KV, Dc), page_map),
            pl.BlockSpec((1, page, KV), stat_map),
            pl.BlockSpec((1, page, KV), stat_map),
            pl.BlockSpec((1, page, KV, Dc), page_map),
            pl.BlockSpec((1, page, KV), stat_map),
            pl.BlockSpec((1, page, KV), stat_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, KV, G), part_map),
            pl.BlockSpec((1, 1, KV, G), part_map),
            pl.BlockSpec((1, 1, n_planes, KV, G, Dc), acc_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((n_planes, KV, G, Dc), jnp.float32),
        ],
    )
    m_part, l_part, acc_part = pl.pallas_call(
        functools.partial(_kernel, bits=kv_bits, k=k, page=page,
                          pages_per_split=pages_per_split,
                          logit_cap=logit_cap, scale=D ** -0.5),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, splits, KV, G), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, KV, G), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, n_planes, KV, G, Dc),
                                 jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(block_tables, q_pos, window, qg, k_codes, k_mu, k_sigma, v_codes,
      v_mu, v_sigma)

    # combine epilogue: rescale each split's partials to the global max
    m_max = jnp.max(m_part, axis=1, keepdims=True)
    alpha = jnp.exp(m_part - m_max)                        # 0 for dry splits
    l = jnp.sum(alpha * l_part, axis=1)
    acc = jnp.sum(alpha[:, :, None, ..., None] * acc_part, axis=1)
    out = acc / jnp.maximum(l, 1e-30)[:, None, ..., None]
    # back from planar to natural column order (B, KV, G, D)
    out = jnp.moveaxis(out, 1, -1).reshape(B, KV, G, D)
    return out.astype(q.dtype).reshape(B, 1, H, D)
