"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md Sec. 5): activations stay sharded over (pod, data) on
batch and *replicated* over `model`; expert weights are sharded over `model`
on the expert axis (E_l = E / tp experts per shard) and over `data` on d_ff
(FSDP).  Each model shard:

  1. computes the (replicated) router top-k for all row-local tokens,
  2. packs the token-copies routed to *its own* experts into a fixed
     (E_l, C, d) capacity buffer via sort + scatter (no one-hot dispatch
     tensor — at 384 experts x 32k tokens a GShard-style one-hot would be
     TBs; the sort-based pack is O(T k log T k) and static-shaped),
  3. runs the batched expert SwiGLU on the MXU,
  4. scatter-adds weighted outputs back to token positions and
     all-reduces over `model` (replacing the usual return all_to_all —
     the same (T, d) all-reduce TP attention already pays).

The core (``moe_ffn_local``) is shard-agnostic: n_shards=1 turns it into
the single-device dropping MoE used in smoke tests; the shard_map wrapper
in repro/parallel wires it to the mesh.  Dropped tokens (capacity overflow)
fall back to the residual path, standard for capacity-based MoE.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


Array = jax.Array


class MoEConfig(NamedTuple):
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    renorm_topk: bool = True       # normalize top-k router weights to sum 1


def router_topk(x: Array, router_w: Array, cfg: MoEConfig):
    """(T, d) -> (weights (T, k) f32, ids (T, k) int32).  Router math fp32."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renorm_topk:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, ids


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def moe_ffn_local(x: Array, router_w: Array, gate_w: Array, up_w: Array,
                  down_w: Array, cfg: MoEConfig, *, shard_idx=0,
                  n_shards: int = 1, act_fn=jax.nn.silu) -> Array:
    """Local-expert MoE contribution.

    x        : (T, d) tokens (this data-row's tokens, replicated over model)
    router_w : (d, E) full router
    gate/up  : (E_l, d, f) local expert slices;  down : (E_l, f, d)
    returns  : (T, d) — contribution of the local experts only; caller
               psums over the `model` axis when n_shards > 1.
    """
    T, d = x.shape
    E = cfg.n_experts
    E_l = gate_w.shape[0]
    k = cfg.top_k
    C = capacity(T, cfg)

    gates, ids = router_topk(x, router_w, cfg)           # (T, k)
    flat_ids = ids.reshape(-1)                           # (T*k,)
    flat_gates = gates.reshape(-1)
    flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k

    lo = jnp.asarray(shard_idx, jnp.int32) * E_l
    local_e = flat_ids - lo
    is_local = (local_e >= 0) & (local_e < E_l)
    sort_key = jnp.where(is_local, local_e, E_l)         # non-local last
    order = jnp.argsort(sort_key)                        # (T*k,)
    se = sort_key[order]
    stok = flat_tok[order]
    sgate = flat_gates[order]

    # position within expert group: i - first index of that group
    starts = jnp.searchsorted(se, jnp.arange(E_l + 1, dtype=se.dtype))
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[jnp.clip(se, 0, E_l)]
    keep = (se < E_l) & (pos < C)
    buf_idx = jnp.where(keep, se * C + pos, E_l * C)     # OOB -> dropped

    # pack tokens into the capacity buffer
    xg = jnp.take(x, stok, axis=0)                       # (T*k, d)
    buf = jnp.zeros((E_l * C, d), x.dtype).at[buf_idx].set(xg, mode="drop")
    buf = buf.reshape(E_l, C, d)

    # batched expert SwiGLU on the MXU
    cd = x.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, gate_w.astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, up_w.astype(cd))
    h = act_fn(g) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, down_w.astype(cd))
    y_flat = y_buf.reshape(E_l * C, d)

    # unpack: copy i contributes gate_i * y_buf[buf_idx_i] to token stok_i
    contrib = jnp.take(y_flat, jnp.minimum(buf_idx, E_l * C - 1), axis=0)
    contrib = contrib * (sgate * keep).astype(cd)[:, None]
    y = jnp.zeros((T, d), cd).at[stok].add(contrib, mode="drop")
    return y


def moe_ffn(x: Array, router_w: Array, gate_w: Array, up_w: Array,
            down_w: Array, cfg: MoEConfig, *, axis_name: Optional[str] = None,
            act_fn=jax.nn.silu) -> Array:
    """MoE FFN on (B, S, d); inside shard_map pass ``axis_name='model'``."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    if axis_name is None:
        y = moe_ffn_local(xt, router_w, gate_w, up_w, down_w, cfg,
                          shard_idx=0, n_shards=1, act_fn=act_fn)
    else:
        idx = jax.lax.axis_index(axis_name)
        n = jax.lax.axis_size(axis_name)
        y = moe_ffn_local(xt, router_w, gate_w, up_w, down_w, cfg,
                          shard_idx=idx, n_shards=n, act_fn=act_fn)
        y = jax.lax.psum(y, axis_name)
    return y.reshape(B, S, d)


def aux_load_balance_loss(x: Array, router_w: Array, cfg: MoEConfig) -> Array:
    """Switch-style load-balancing auxiliary loss (mean over tokens)."""
    logits = jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                     router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
    _, ids = jax.lax.top_k(probs, cfg.top_k)
    me = jnp.mean(probs, axis=0)                         # mean router prob
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], cfg.n_experts), axis=0)
    return cfg.n_experts * jnp.sum(me * ce)
