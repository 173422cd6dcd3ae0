"""Custom collectives: UNIQ-compressed cross-pod gradient synchronisation
(beyond-paper, DESIGN.md Sec. 9).

The `pod` mesh axis is pure data parallelism over DCN — the slowest link in
the system.  Standard DP syncs gradients with a bf16/f32 all-reduce
(ring traffic ~ 2*(n-1)/n * size * dtype_bytes per device).  We instead

    1. quantize each pod's local gradient to int8 with a per-(leading-slice)
       absmax scale — the same absmax codec the optimizer uses for int8
       momentum,
    2. all_gather codes + scales over `pod`  (traffic ~ (n-1)/n * size * 1B),
    3. dequantize and average locally.

For n=2 pods this moves ~4x fewer DCN bytes than an f32 all-reduce and ~2x
fewer than bf16.  Determinism: every pod computes the identical average, so
optimizer states stay in lockstep without re-broadcast.

``shard_map(..., axis_names={'pod'})`` keeps `data`/`model` auto-sharded
(GSPMD) inside, so this wraps the *existing* loss/grad computation without
touching the model code.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Array = jax.Array


def _absmax_quant(g: Array, bits: int = 8):
    qmax = 2.0 ** (bits - 1) - 1.0
    # keepdims always: scale must broadcast against codes after the
    # leading all_gather axis is prepended
    axes = tuple(range(1, g.ndim)) if g.ndim >= 2 else (0,)
    amax = jnp.max(jnp.abs(g), axis=axes, keepdims=True)
    scale = jnp.maximum(amax.astype(jnp.float32), 1e-30) / qmax
    codes = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -qmax, qmax)
    return codes.astype(jnp.int8), scale


def _pmean_2d(g: Array, axis_name: str) -> Array:
    """pmean that routes rank<2 operands through a (1, n) reshape —
    sub-2-D collectives trip a partial-manual broadcast edge case in
    jax 0.8 when the operand is auto-sharded inside the region."""
    if g.ndim >= 2:
        return jax.lax.pmean(g, axis_name)
    out = jax.lax.pmean(g.reshape(1, -1), axis_name)
    return out.reshape(g.shape)


def compressed_pmean(tree: Any, axis_name: str, bits: int = 8) -> Any:
    """Mean of a gradient pytree across ``axis_name`` via int8 all_gather.

    Must run inside a shard_map region where ``axis_name`` is manual.
    """
    n = jax.lax.axis_size(axis_name)

    def one(g):
        if g.ndim <= 1 or g.size <= 128 or not jnp.issubdtype(
                g.dtype, jnp.floating):
            # small/1-D/integer leaves: exact psum (<0.01% of traffic).
            return _pmean_2d(g, axis_name)
        shape = g.shape
        codes, scale = _absmax_quant(g, bits)
        codes_g = jax.lax.all_gather(codes, axis_name)   # (n, ...)
        scale_g = jax.lax.all_gather(scale, axis_name)
        # explicit rank alignment: gather layouts differ between pure- and
        # partial-manual shard_map contexts
        codes_g = codes_g.reshape((n,) + codes.shape)
        scale_g = scale_g.reshape((n,) + scale.shape)
        deq = codes_g.astype(jnp.float32) * scale_g
        return (jnp.sum(deq, axis=0) / n).astype(g.dtype).reshape(shape)

    return jax.tree.map(one, tree)


def make_pod_compressed_grads(loss_and_grads_fn, mesh, bits: int = 8):
    """Wrap ``loss_and_grads_fn(params, batch, rng) -> (loss, grads)`` so the
    batch is split across `pod` and gradients sync via compressed_pmean.

    `data`/`model` stay auto-sharded (GSPMD) inside the region; only `pod`
    is manual.  Falls through unchanged when the mesh has no pod axis.
    """
    from jax.sharding import PartitionSpec as P
    if mesh is None or "pod" not in mesh.axis_names:
        return loss_and_grads_fn

    def region(params, batch, rng):
        loss, grads = loss_and_grads_fn(params, batch, rng)
        grads = compressed_pmean(grads, "pod", bits)
        loss = _pmean_2d(loss, "pod")
        return loss, grads

    def wrapped(params, batch, rng):
        batch_specs = jax.tree.map(
            lambda x: P("pod", *(None,) * (x.ndim - 1)), batch)
        return pod_shard_map(
            region, mesh,
            in_specs=(P(), batch_specs, P()),
            out_specs=(P(), P()))(params, batch, rng)

    return wrapped


def pod_shard_map(f, mesh, in_specs, out_specs, manual=("pod",)):
    """shard_map with only ``manual`` axes manual (partial-manual region)."""
    return jax.shard_map(f, mesh=mesh, axis_names=set(manual),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)
