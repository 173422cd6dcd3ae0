"""Attention: GQA with RoPE, sliding window, logit soft-capping, KV cache.

Three execution paths, one math:

  * ``full_attention``    — materialized (B, H, Sq, Sk) scores; fine for
    train_4k-sized tiles.
  * ``chunked_attention`` — lax.scan over KV blocks with online softmax
    (flash-style, O(Sq * block) live scores); used for long prefill.
    This is the memory-hierarchy adaptation: on TPU the chunk loop becomes
    a VMEM-resident pipeline under XLA; a hand-written Pallas flash kernel
    is unnecessary for the dry-run (jnp lowers to the same fused HLO
    structure) and the paper's contribution is elsewhere.
  * ``decode_attention``  — one query position against a (possibly much
    longer) cache; linear in S.

Layout: q (B, Sq, H, D), k/v (B, Sk, KV, D); GQA groups G = H // KV.
``window``: None for global attention, else causal sliding window width
(gemma-2 local layers).  ``softcap``: attention-logit soft-capping.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import softcap as _softcap

Array = jax.Array

NEG_INF = -1e30


class AttnParams(NamedTuple):
    window: Optional[int] = None      # sliding-window width (local attention)
    logit_cap: Optional[float] = None # gemma-2 soft-capping
    causal: bool = True


def _mask(q_pos: Array, k_pos: Array, p: AttnParams) -> Array:
    """(..., Sq, Sk) boolean validity mask from position vectors.

    ``q_pos`` may be (Sq,) — shared positions for every batch row — or
    (B, Sq) per-sequence positions (the batched chunked-prefill path,
    where each row of a coalesced chunk sits at a different prompt
    offset); ``k_pos`` is (Sk,).
    """
    qp = q_pos[..., :, None]
    m = jnp.ones(qp.shape[:-1] + (k_pos.shape[-1],), jnp.bool_)
    if p.causal:
        m &= qp >= k_pos
    if p.window is not None:
        m &= qp - k_pos < p.window
    return m


def _scores(q: Array, k: Array, p: AttnParams) -> Array:
    """q (B, Sq, H, D) x k (B, Sk, KV, D) -> (B, H, Sq, Sk) f32 logits."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    s = s * (D ** -0.5)
    s = _softcap(s, p.logit_cap)
    return s.reshape(B, H, Sq, k.shape[1])


def _attend(q: Array, k: Array, v: Array, mask: Array,
            p: AttnParams) -> Array:
    """Materialized-scores attention under a precomputed validity mask of
    shape (Sq, Sk) (shared) or (B, Sq, Sk) (per-sequence positions)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    s = _scores(q, k, p)                                  # (B,H,Sq,Sk) f32
    if mask.ndim == 2:
        mask = mask[None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    a = a.reshape(B, KV, G, Sq, k.shape[1])
    out = jnp.einsum("bkgqs,bskd->bqkgd", a, v)
    return out.reshape(B, Sq, H, D)


def full_attention(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
                   p: AttnParams) -> Array:
    """Materialized-scores attention.  positions: (Sq,) (or (B, Sq) for
    per-sequence chunk offsets), (Sk,) int32."""
    return _attend(q, k, v, _mask(q_pos, k_pos, p), p)


def chunked_attention(q: Array, k: Array, v: Array, q_pos: Array,
                      k_pos: Array, p: AttnParams,
                      kv_chunk: int = 1024) -> Array:
    """Online-softmax attention, scanning KV in chunks (flash-style)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    if Sk % kv_chunk:
        kv_chunk = Sk  # fallback: single chunk
    n_chunks = Sk // kv_chunk

    qg = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, Sq, KV, G, D)
    kc = k.reshape(B, n_chunks, kv_chunk, KV, D)
    vc = v.reshape(B, n_chunks, kv_chunk, KV, D)
    pc = k_pos.reshape(n_chunks, kv_chunk)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, pos_blk = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_blk.astype(jnp.float32))
        s = _softcap(s, p.logit_cap)
        mask = _mask(q_pos, pos_blk, p)                   # (Sq, kc)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)                       # (B,KV,G,Sq)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (all -inf): keep m finite
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)
        pexp = jnp.exp(s - m_safe[..., None])
        pexp = jnp.where(mask[None, None, None], pexp, 0.0)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", pexp, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, -2, 1)                        # (B,Sq,KV,G,D)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def paged_decode_attention(q: Array, k_pool: Array, v_pool: Array,
                           layer: Array, block_tables: Array, q_pos: Array,
                           p: AttnParams) -> Array:
    """Decode attention against one layer of a paged KV pool.

    q            : (B, 1, H, D) current-position queries.
    k/v_pool     : (L, P, page, KV, D) device-resident page pool of every
                   layer (all slots share it; a sequence's KV lives in
                   the pages its block table names, page j covering
                   positions [j*page, (j+1)*page)).
    layer        : () int32 layer whose pages are read.
    block_tables : (B, n_pages) int32 page ids per sequence; entries past
                   the allocated prefix point at the reserved sink page 0
                   and are masked out by position below.
    q_pos        : (B,) current positions.

    One gather ``k_pool[layer, block_tables]`` reads the table's pages of
    that layer straight from the whole pool (the decode scan carries it),
    so no per-layer slice is materialized.  The gathered view is
    position-contiguous by construction, so the plain masked
    ``decode_attention`` applies unchanged: keys at positions > q_pos
    (never-written or sink rows) are masked to -inf exactly as
    out-of-prefix rows are in the slot cache.
    """
    B = q.shape[0]
    _, _, page, KV, D = k_pool.shape
    n_pages = block_tables.shape[1]
    k = k_pool[layer, block_tables].reshape(B, n_pages * page, KV, D)
    v = v_pool[layer, block_tables].reshape(B, n_pages * page, KV, D)
    return decode_attention(q, k, v, q_pos, p)


def paged_decode_attention_quant(q: Array, cache, block_tables: Array,
                                 q_pos: Array, p: AttnParams, *,
                                 kv_bits: int,
                                 use_pallas: Optional[bool] = None,
                                 interpret: bool = False) -> Array:
    """Decode attention against a k-quantile-coded paged KV pool.

    q            : (B, 1, H, D) current-position queries.
    cache        : one layer of the quantized pool —
                   {"k_codes","v_codes"} (P, page, KV, D') int8/uint8 and
                   {"k_mu","k_sigma","v_mu","v_sigma"} (P, page, KV) bf16
                   (see models/kv_cache.py; D' = D//2 packed for 4-bit).
                   The decode scan passes ``pool[l]`` of the pool it
                   carries: a read of that layer, never stacked back.
    block_tables : (B, n_pages) int32 page ids; sink-page entries are
                   masked out by position exactly as in the dense path.

    On TPU this runs the fused Pallas kernel: per (batch, page) grid
    step the block table gathers the page's code tile HBM->VMEM,
    unpack+dequant happens on the VPU, and an online-softmax accumulates
    across pages — the KV pool is never materialized densely.  The
    sliding window rides as a traced scalar (the decode scan's per-layer
    window value, BIG_WINDOW for global layers), so one compiled kernel
    serves every layer.  Elsewhere the jnp reference gathers +
    dequantizes and reuses ``decode_attention`` unchanged; both share
    the codec in models/kv_cache.py, so they agree bit-for-bit on what
    every code dequantizes to.
    """
    from repro.models import kv_cache as kvq
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from repro.kernels import paged_attn
        return paged_attn.paged_quant_attention(
            q, cache["k_codes"], cache["k_mu"], cache["k_sigma"],
            cache["v_codes"], cache["v_mu"], cache["v_sigma"],
            block_tables, q_pos, kv_bits=kv_bits, window=p.window,
            logit_cap=p.logit_cap, interpret=interpret)
    B = q.shape[0]
    P, page, KV = cache["k_mu"].shape
    n_pages = block_tables.shape[1]
    S = n_pages * page

    def gather_dequant(codes, mu, sigma):
        c = codes[block_tables].reshape(B, S, KV, codes.shape[-1])
        m = mu[block_tables].reshape(B, S, KV)
        s = sigma[block_tables].reshape(B, S, KV)
        return kvq.dequantize_kv(c, m, s, kv_bits, dtype=q.dtype)

    k = gather_dequant(cache["k_codes"], cache["k_mu"], cache["k_sigma"])
    v = gather_dequant(cache["v_codes"], cache["v_mu"], cache["v_sigma"])
    return decode_attention(q, k, v, q_pos, p)


def paged_prefill_attention(q: Array, k_pool: Array, v_pool: Array,
                            layer: Array, block_tables: Array, q_pos: Array,
                            p: AttnParams) -> Array:
    """Multi-token (chunked) prefill attention against one layer of a
    paged KV pool.

    q            : (B, C, H, D) one chunk of prompt queries.
    k/v_pool     : (L, P, page, KV, D) page pool of every layer; the
                   chunk's own rows must already be written in
                   (write-before-read).
    layer        : () int32 layer whose pages are read.
    block_tables : (B, n_pages) page ids; sink entries masked by position.
    q_pos        : (C,) absolute positions of the chunk's tokens, or
                   (B, C) per-sequence positions when several coalesced
                   sequences' chunks sit at different prompt offsets.

    One gather ``k_pool[layer, block_tables]`` reads the layer's pages
    from the whole pool, as in ``paged_decode_attention``.  The gathered
    view is position-contiguous (page j of the table covers positions
    [j*page, (j+1)*page)), so ``full_attention``'s causal
    ``k_pos <= q_pos`` mask makes the chunk see exactly the rows a whole
    prefill of the same prefix would: earlier chunks' pages, plus this
    chunk's freshly written rows; later rows (other sequences' content in
    a partially-shared page, sink garbage) are masked to -inf.
    """
    B = q.shape[0]
    _, _, page, KV, D = k_pool.shape
    n_pages = block_tables.shape[1]
    Sk = n_pages * page
    k = k_pool[layer, block_tables].reshape(B, Sk, KV, D)
    v = v_pool[layer, block_tables].reshape(B, Sk, KV, D)
    k_pos = jnp.arange(Sk, dtype=jnp.int32)
    return full_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                          q_pos, k_pos, p)


def paged_prefill_attention_quant(q: Array, cache, block_tables: Array,
                                  q_pos: Array, p: AttnParams, *,
                                  kv_bits: int) -> Array:
    """Chunked-prefill attention against a k-quantile-coded paged pool.

    ``q_pos`` is (C,) or (B, C) exactly as in ``paged_prefill_attention``.
    Gathers + dequantizes the block-table row densely and defers to
    ``full_attention`` — exactly what the whole-prefill path sees after
    ``fake_quant_kv``, so chunked and whole prefill agree in the codes
    domain: the *stored* codes+stats are byte-identical (tier-1 pinned);
    the attention outputs themselves may differ by reduction-order ulps
    where the two paths reduce over different padded key widths.  The
    chunk length is one page or a few, so the dense gather is small; the
    fused Pallas path stays a decode-only optimization.
    """
    from repro.models import kv_cache as kvq
    B = q.shape[0]
    _, page, KV = cache["k_mu"].shape
    n_pages = block_tables.shape[1]
    Sk = n_pages * page

    def gather_dequant(codes, mu, sigma):
        c = codes[block_tables].reshape(B, Sk, KV, codes.shape[-1])
        m = mu[block_tables].reshape(B, Sk, KV)
        s = sigma[block_tables].reshape(B, Sk, KV)
        return kvq.dequantize_kv(c, m, s, kv_bits, dtype=q.dtype)

    k = gather_dequant(cache["k_codes"], cache["k_mu"], cache["k_sigma"])
    v = gather_dequant(cache["v_codes"], cache["v_mu"], cache["v_sigma"])
    k_pos = jnp.arange(Sk, dtype=jnp.int32)
    return full_attention(q, k, v, q_pos, k_pos, p)


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     q_pos: Array, p: AttnParams,
                     cache_len: Optional[Array] = None) -> Array:
    """Single-position decode: q (B, 1, H, D) vs cache (B, S, KV, D).

    q_pos: (B,) current positions.  Keys at positions > q_pos (or outside
    the sliding window) are masked; the cache may be longer than the valid
    prefix.
    """
    B, _, H, D = q.shape
    S = k_cache.shape[1]
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = _softcap(s, p.logit_cap)
    k_pos = jnp.arange(S)[None]                          # (1, S)
    valid = k_pos <= q_pos[:, None]
    if p.window is not None:
        valid &= (q_pos[:, None] - k_pos) < p.window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", a, v_cache)
    return out.reshape(B, 1, H, D)
