"""Decoder-only LM assembly (dense / MoE / VLM-stub families).

Layers are scan-stacked: every per-layer parameter carries a leading (L,)
axis and the forward pass is one ``lax.scan`` over layers — this keeps the
lowered HLO size O(1) in depth (61-layer / 1T-param configs compile in
minutes on one CPU core) and gives the UNIQ gradual schedule a natural
per-layer mode vector.

Serving-time weights may be k-quantile-coded: any weight leaf replaced by a
``{"q_codes", "q_mu", "q_sigma"}`` dict (see ``code_weight``) is
dequantized inside the layer body by ``materialize`` (the jnp reference
dequant) and multiplied with ``jnp.dot`` — on every backend, TPU included;
the qmatmul Pallas kernels are not on the served path.  Whether XLA fuses
the dequant into the dot operand, and so keeps W4's 4x cut in HBM weight
traffic, is for a chip profile to show.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import packing
from repro.kernels import ref as kref
from repro.models import attention as attn
from repro.models import kv_cache as kvq
from repro.models import moe as moe_lib
from repro.models.layers import (apply_rope, dense_init, embed_init,
                                 layer_norm, rms_norm, softcap, swiglu)

Array = jax.Array

BIG_WINDOW = 1 << 30


@dataclasses.dataclass(frozen=True)
class ModelOpts:
    """Runtime (non-architecture) options."""
    compute_dtype: Any = jnp.bfloat16
    a_bits: int = 32                  # activation fake-quant (32 = off)
    remat: bool = True                # checkpoint each scan layer
    kv_chunk: int = 1024              # chunked-attention KV block
    attn_chunked_min_len: int = 8192  # use chunked attention above this S
    ssd_chunk: int = 128
    ce_chunk: int = 1024              # cross-entropy chunk along S
    moe_axis: Optional[str] = None    # 'model' => shard_map EP (needs mesh)
    mesh: Any = None                  # jax Mesh for explicit-EP regions
    fsdp_axes: tuple = ("data",)      # axes expert weights are FSDP-sharded on
    manual_axes: tuple = ()           # mesh axes already manual (shard_map)
    serve_w_bits: int = 16            # 4/8 => quantized serving weights
    serve_a_bits: int = 32            # serving activation codec on quantized
                                      #   matmuls: 8 => per-token int8 codes
                                      #   + absmax scale before the dot (the
                                      #   qmatmul_a8 regime; threaded from
                                      #   EngineConfig.a_bits / --a-bits)
    kv_bits: int = 16                 # 8/4 => k-quantile-coded KV cache
                                      #   (paged serving; per-row per-head
                                      #   stats, see models/kv_cache.py)
    moe_mode: str = "gather"          # gather: all-gather FSDP'd expert
                                      #   weights per layer (baseline);
                                      # reduce: keep d_ff sharded over data,
                                      #   psum partial outputs instead —
                                      #   kills the per-layer weight gathers
                                      #   (EXPERIMENTS.md Perf iteration)
    uniq: Any = None                  # UniqConfig => apply the UNIQ weight
                                      #   transform INSIDE the layer scan
                                      #   (per-layer transient, remat'd)
                                      #   instead of on the whole tree
    dp_includes_model: bool = False   # fsdp-only layout: batch over
                                      #   (pod,data,model); 'tp' constraints
                                      #   become no-ops


# --------------------------------------------------------------------------
# Activation sharding constraints
# --------------------------------------------------------------------------

def shard_act(x: Array, opts: "ModelOpts", *axes) -> Array:
    """Constrain an activation's sharding ('dp'/'tp' sentinels per dim).

    No-op when opts.mesh is None (CPU tests).  Divisibility-checked so odd
    dims (B=1 decode, KV heads < tp) degrade to replicated instead of
    erroring — matching the parameter-rule behaviour.
    """
    mesh = opts.mesh
    if mesh is None:
        return x
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp_names = ("pod", "data", "model") if opts.dp_includes_model \
        else ("pod", "data")
    resolved = []
    for i, a in enumerate(axes):
        if a == "dp":
            dp = [ax for ax in dp_names if ax in mesh.axis_names
                  and ax not in opts.manual_axes]
            while dp and x.shape[i] % int(
                    np.prod([mesh.shape[ax] for ax in dp])):
                dp.pop()
            resolved.append(tuple(dp) if dp else None)
        elif a == "tp":
            ok = ("model" in mesh.axis_names
                  and not opts.dp_includes_model
                  and x.shape[i] % mesh.shape["model"] == 0)
            resolved.append("model" if ok else None)
        else:
            resolved.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# --------------------------------------------------------------------------
# Quantized-weight matmul dispatch
# --------------------------------------------------------------------------

def is_qweight(w) -> bool:
    return isinstance(w, dict) and "q_codes" in w


def materialize(w, dtype):
    """Return a dense (possibly dequantized) weight in compute dtype; a
    coded leaf is dequantized under the ``dequant`` scope."""
    if not is_qweight(w):
        return w.astype(dtype)
    with jax.named_scope("dequant"):
        return _dequantize(w, dtype)


def _dequantize(w, dtype):
    codes = w["q_codes"]
    bits = 4 if codes.dtype == jnp.uint8 else 8
    if bits == 4:
        codes = packing.unpack_int4(codes)
    if "q_lut" in w:
        # Codebook layout (dist="empirical"): levels are order statistics
        # with no analytic form; dequant is a per-code LUT gather, the
        # jnp formulation of kernels.qmatmul_lut.
        idx = codes.astype(jnp.int32)
        if bits == 8:
            idx = idx + 128                 # undo int8 storage offset
        lut = w["q_lut"]
        if lut.ndim == 1:                   # per-tensor codebook (k,)
            return lut[idx].astype(dtype)
        # stacked per-layer codebooks (L, k) against codes (L, ...)
        flat = lut[jnp.arange(lut.shape[0])[:, None],
                   idx.reshape(idx.shape[0], -1)]
        return flat.reshape(idx.shape).astype(dtype)
    return kref.kquantile_dequant_ref(codes, w["q_mu"], w["q_sigma"],
                                      2 ** bits, dtype=dtype)


def mm(x: Array, w) -> Array:
    """x @ w where w is a dense array or a quantized-weight dict."""
    with jax.named_scope("matmul"):
        return jnp.dot(x, materialize(w, x.dtype))


def mm_a(x: Array, w, opts: "ModelOpts") -> Array:
    """``mm`` with the serving activation codec (the A8 path).

    With ``opts.serve_a_bits < 32`` and a quantized weight dict, the
    activation is round-tripped through the real integer codec per token
    (absmax scale over the feature axis, core/activations.py) before the
    dot — the jnp formulation of ``kernels.qmatmul_a8``, so ``--a-bits 8``
    serving numerics match the W4A8/W8A8 kernel regime and the BOPs
    accounting's b_a term describes what was actually computed.  Dense
    (unquantized) weights and serve_a_bits >= 32 fall through to ``mm``.
    """
    bits = opts.serve_a_bits
    if bits >= 32 or not is_qweight(w):
        return mm(x, w)
    from repro.core import activations as act
    with jax.named_scope("matmul"):
        codes, scale = act.quant_act(x, bits,
                                     act.act_scale(x, bits, axis=-1))
        a = codes.astype(jnp.float32) * scale
        return jnp.dot(a, materialize(w, jnp.float32),
                       preferred_element_type=jnp.float32).astype(x.dtype)


def _code_tensor(w, bits: int, dist: str):
    """Serving dict of one weight tensor (no layer axis); Gaussian
    statistics are per output channel."""
    if dist == "empirical":
        from repro.core import quantizers as Q
        from repro.core.distributions import EmpiricalModel
        m = EmpiricalModel.fit(w)
        codes = Q.kquantile_quantize(w, m, 2 ** bits, code_dtype=jnp.uint8)
        return {"q_codes": packing.store_codes(codes, bits),
                "q_lut": m.level_values(2 ** bits).astype(jnp.float32)}
    if dist != "gaussian":
        raise ValueError(f"dist must be gaussian|empirical, got {dist!r}")
    from repro.core.uniq import quantize_tensor
    qt = quantize_tensor(w, bits, per_channel=True)
    return {"q_codes": qt.codes, "q_mu": qt.mu, "q_sigma": qt.sigma}


_code_tensor_jit = jax.jit(_code_tensor, static_argnums=(1, 2))


def code_weight(w, bits: int, *, dist: str = "gaussian",
                stacked: bool = False):
    """k-quantile code one weight leaf into its serving dict.

    The one coder of the repo: ``quantize_params_for_serving`` calls it,
    for trained trees and for ``build_serving_params``'s random weights
    built on the device.  A ``stacked`` leaf (leading layer axis) is coded
    one layer at a time, so only one layer's float temporaries are live;
    its statistics are per (layer, channel) either way.  Every layer goes
    through the same compiled program from a materialized input, so the
    codes do not depend on where the float weights came from.

    dist="gaussian" (paper-faithful): a view of ``core.uniq.
    QuantizedTensor``, ``{"q_codes", "q_mu", "q_sigma"}``; dequant is
    analytic.  dist="empirical": codes are taken against the per-tensor
    (per-layer when stacked) empirical CDF and the dict carries the
    k-level codebook instead (``{"q_codes", "q_lut"}`` — the paper's
    "look-up table availability" assumption), matching how
    ``cfg.dist="empirical"`` trains (core.uniq.transform_param).  Codes
    use the weight-path storage conventions (int4 packing, int8 k=256
    offset), so ``kernels.qmatmul_lut`` consumes them unchanged.
    """
    if not stacked:
        return _code_tensor_jit(w, bits, dist)
    layers = [_code_tensor_jit(w[i], bits, dist) for i in range(w.shape[0])]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _leaf_role(path: str, w, bits: int) -> str:
    """How a leaf is served at ``bits``: "code" (k-quantile dict),
    "dense" (a matmul weight left uncoded) or "keep" (norm scales,
    routers)."""
    from repro.core.uniq import default_quant_filter
    if not default_quant_filter(path, w):
        return "keep"
    # only int4 packing needs an even trailing dim
    if bits >= 16 or (bits == 4 and w.shape[-1] % 2):
        return "dense"
    return "code"


def quantize_params_for_serving(params, bits: int, dist: str = "gaussian",
                                stacked_prefixes=("layers", "enc_layers",
                                                  "dec_layers")):
    """Replace the eligible weight leaves of a trained tree by k-quantile
    code dicts (``code_weight``); other leaves are kept as they are."""
    from repro.core.uniq import path_str
    if dist not in ("gaussian", "empirical"):
        raise ValueError(f"dist must be gaussian|empirical, got {dist!r}")

    def one(kp, leaf):
        p = path_str(kp)
        if _leaf_role(p, leaf, bits) != "code":
            return leaf
        stacked = any(p.startswith(pre) for pre in stacked_prefixes)
        return code_weight(leaf, bits, dist=dist, stacked=stacked)
    return jax.tree_util.tree_map_with_path(one, params)


# dense matmul weights of a built serving tree are served in the default
# compute dtype (bf16)
SERVE_DENSE_DTYPE = ModelOpts().compute_dtype


def build_serving_params(rng: Array, cfg: ArchConfig, bits: int,
                         dist: str = "gaussian"):
    """Random-init the decoder-only tree and put it in serving form on the
    device, one layer at a time.

    The weights are ``init_params``'s, drawn from the same keys, and they
    are coded by ``quantize_params_for_serving``, so the codes and
    statistics are bit-identical to coding ``init_params(rng, cfg)``.  No
    f32 layer stack ever exists: each layer, and then each leaf outside
    the stack (embedding, final norm, LM head), is drawn alone by one
    program, coded (bits 4/8), and written into the stacked served tree,
    whose buffers are donated from layer to layer.  Matmul weights left
    dense (bits 16, or an odd trailing dim at bits 4) are cast to
    ``SERVE_DENSE_DTYPE`` inside the drawing program, so they never leave
    it in f32; norm scales stay f32.  Only the weights to be coded leave
    it in f32, as the coder's materialized input: at most one layer
    (0.79 GB at granite_3_8b width) or one top-level leaf (the 0.81 GB
    embedding) at a time.  At that width the W4 tree is 4.5 GB, where the
    f32 init alone would need 33.5 GB.

    The coder is not fused into the drawing program: there XLA splits the
    statistics' reductions differently (a reduce-window tree on the CPU),
    and the embedding's ``q_sigma`` then differs from a trained tree's
    coding in its last bit.  Coding a materialized input is what keeps
    the two paths one program.
    """
    keys = jax.random.split(rng, 16)
    layers = None
    for i, key in enumerate(_layer_keys(keys, cfg)):
        layer = quantize_params_for_serving(
            _draw_layer(key, cfg, bits), bits, dist,
            stacked_prefixes=())["layers"]
        if layers is None:
            layers = jax.tree.map(
                lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype),
                layer)
        # the host would otherwise enqueue later layers' draws while the
        # device is still coding this one, and each draw's output is
        # allocated when it is enqueued
        layers = jax.block_until_ready(_set_layer(layers, layer, i))
    params = {"layers": layers}
    for name in jax.eval_shape(lambda k: _init_top(k, cfg), keys):
        params.update(jax.block_until_ready(quantize_params_for_serving(
            _draw_top_leaf(keys, cfg, bits, name), bits, dist)))
    return params


def _cast_dense(tree, bits: int):
    """``tree`` with its leaves that stay dense at ``bits`` in
    ``SERVE_DENSE_DTYPE``."""
    from repro.core.uniq import path_str
    return jax.tree_util.tree_map_with_path(
        lambda kp, w: w.astype(SERVE_DENSE_DTYPE)
        if _leaf_role(path_str(kp), w, bits) == "dense" else w, tree)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_layer(key, cfg: ArchConfig, bits: int):
    return _cast_dense({"layers": init_layer(key, cfg)}, bits)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_top_leaf(keys, cfg: ArchConfig, bits: int, name: str):
    return _cast_dense({name: _init_top(keys, cfg)[name]}, bits)


@functools.partial(jax.jit, donate_argnums=0)
def _set_layer(stack, layer, i):
    return jax.tree.map(lambda s, x: s.at[i].set(x), stack, layer)


# --------------------------------------------------------------------------
# Parameter initialization
# --------------------------------------------------------------------------

def norm_param(cfg: ArchConfig, *shape):
    """Norm parameter(s): dict for LayerNorm, bare scale for RMSNorm."""
    if cfg.norm_kind == "layer":
        return {"scale": jnp.ones(shape, jnp.float32),
                "bias": jnp.zeros(shape, jnp.float32)}
    return jnp.ones(shape, jnp.float32)


def _layer_matmuls(cfg: ArchConfig) -> Dict[str, Any]:
    """name -> (init key index, per-layer shape) of a layer's matmul
    weights (and router); every fan-in is the second-to-last axis."""
    d, f = cfg.d_model, cfg.d_ff
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes = {"wq": (0, (d, H * hd)), "wk": (1, (d, KV * hd)),
              "wv": (2, (d, KV * hd)), "wo": (3, (H * hd, d))}
    if cfg.is_moe:
        E = cfg.n_experts
        shapes.update(router=(4, (d, E)), eg=(5, (E, d, f)),
                      eu=(6, (E, d, f)), ed=(7, (E, f, d)))
    else:
        shapes.update(w_gate=(5, (d, f)), w_up=(6, (d, f)),
                      w_down=(7, (f, d)))
    return shapes


def init_layer(rng: Array, cfg: ArchConfig) -> Dict[str, Any]:
    """One decoder layer's parameters (no leading layer axis), drawn from
    the layer's own key."""
    d = cfg.d_model
    keys = jax.random.split(rng, 8)
    params = {"attn_norm": norm_param(cfg, d), "mlp_norm": norm_param(cfg, d)}
    if cfg.post_norms:
        params["post_attn_norm"] = jnp.ones((d,), jnp.float32)
        params["post_mlp_norm"] = jnp.ones((d,), jnp.float32)
    params.update({name: dense_init(keys[i], shape)
                   for name, (i, shape) in _layer_matmuls(cfg).items()})
    return params


def _layer_keys(keys: Array, cfg: ArchConfig) -> Array:
    return jax.random.split(keys[10], cfg.n_layers)


def _init_top(keys: Array, cfg: ArchConfig) -> Dict[str, Any]:
    """The parameters outside the layer stack."""
    params: Dict[str, Any] = {
        "embed": embed_init(keys[8], (cfg.vocab, cfg.d_model)),
        "final_norm": norm_param(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[9], (cfg.d_model, cfg.vocab))
    return params


def init_params(rng: Array, cfg: ArchConfig) -> Dict[str, Any]:
    """Decoder-only parameter tree (dense / moe / vlm families): the
    stack of ``init_layer`` over per-layer keys, plus the leaves outside
    the stack."""
    keys = jax.random.split(rng, 16)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(_layer_keys(keys, cfg))
    return {**_init_top(keys, cfg), "layers": layers}


# --------------------------------------------------------------------------
# Layer body
# --------------------------------------------------------------------------

def _norm(x, scale_or_dict, cfg: ArchConfig):
    if cfg.norm_kind == "layer":
        return layer_norm(x, scale_or_dict["scale"], scale_or_dict["bias"],
                          cfg.norm_eps)
    zc = cfg.post_norms  # gemma-2 convention: zero-centered scales
    return rms_norm(x, scale_or_dict, cfg.norm_eps, zero_centered=zc)


def _window_schedule(cfg: ArchConfig) -> jnp.ndarray:
    """(L,) per-layer attention window (BIG_WINDOW = global)."""
    import numpy as np
    w = np.full((cfg.n_layers,), BIG_WINDOW, np.int32)
    if cfg.sliding_window and cfg.local_global_alternate:
        w[0::2] = cfg.sliding_window      # even layers local (gemma-2)
    elif cfg.sliding_window:
        w[:] = cfg.sliding_window
    return jnp.asarray(w)


def _attn_block(x, lp, cfg: ArchConfig, opts: ModelOpts, positions, window,
                kv_out: bool = False):
    """Self-attention sub-block on (B, S, d).  Returns (out, kv).

    ``kv`` (when requested) is ``(k, v)`` dense, or the k-quantile code
    dict when ``opts.kv_bits < 16``: serving prefill then fake-quantizes
    K/V *before* attending, so the queries see exactly the dequantized
    rows a later incremental decode (or preemption-resume re-prefill)
    reads from the paged pool — the codes-domain bit-exactness invariant
    (models/kv_cache.py).
    """
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _norm(x, lp["attn_norm"], cfg)
    q = shard_act(mm_a(h, lp["wq"], opts).reshape(B, S, H, hd),
                  opts, "dp", None, "tp", None)
    k = shard_act(mm_a(h, lp["wk"], opts).reshape(B, S, KV, hd),
                  opts, "dp", None, "tp", None)
    v = shard_act(mm_a(h, lp["wv"], opts).reshape(B, S, KV, hd),
                  opts, "dp", None, "tp", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kv = None
    if kv_out:
        if opts.kv_bits < 16:
            k, k_st, k_mu, k_sig = kvq.fake_quant_kv(k, opts.kv_bits)
            v, v_st, v_mu, v_sig = kvq.fake_quant_kv(v, opts.kv_bits)
            kv = {"k_codes": k_st, "v_codes": v_st,
                  "k_mu": k_mu, "k_sigma": k_sig,
                  "v_mu": v_mu, "v_sigma": v_sig}
        else:
            kv = (k, v)
    p = attn.AttnParams(window=window, logit_cap=cfg.attn_logit_cap,
                        causal=True)
    pos1d = positions[0]
    with jax.named_scope("attention"):
        if S >= opts.attn_chunked_min_len:
            o = attn.chunked_attention(q, k, v, pos1d, pos1d, p,
                                       kv_chunk=opts.kv_chunk)
        else:
            o = attn.full_attention(q, k, v, pos1d, pos1d, p)
    o = shard_act(o.reshape(B, S, H * hd), opts, "dp", None, "tp")
    o = shard_act(mm_a(o, lp["wo"], opts), opts, "dp", None, None)
    if cfg.post_norms:
        o = _norm(o, lp["post_attn_norm"], cfg)
    return o, kv


def _moe_ep_sharded(h, router_w, eg, eu, ed, mcfg, opts: ModelOpts):
    """Expert-parallel MoE under shard_map (DESIGN.md Sec. 5).

    Experts sharded over `model` (E_l = E/tp per shard); two FSDP layouts:

    gather (baseline): d_ff sharded over ``opts.fsdp_axes``; weights
      all-gathered inside the region per layer, tokens stay batch-sharded
      over the DP axes.  Weight traffic per layer = full expert bytes.

    reduce: d_ff *stays* sharded; every data shard computes a partial-f
      SwiGLU for all of its pod's tokens (silu/mul are elementwise in f, so
      partial-f is exact) and the (T, d) output partial-sums are psummed
      over (model, data).  Weight traffic: zero; extra activation psum:
      T x d — a huge win when T is small (decode/serve) relative to the
      per-layer expert bytes.  See EXPERIMENTS.md Perf iterations.
    """
    from jax.sharding import PartitionSpec as P
    mesh = opts.mesh
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names
               and a not in opts.manual_axes)
    B = h.shape[0]
    import numpy as np
    fa = tuple(a for a in opts.fsdp_axes if a in mesh.axis_names)
    f_in = fa if fa else None

    if opts.moe_mode == "reduce" and fa:
        # batch sharded over pod only; data axis holds f-slices
        dp_r = tuple(a for a in dp if a not in fa)
        dpn = int(np.prod([mesh.shape[a] for a in dp_r])) if dp_r else 1
        bspec = dp_r if (dp_r and B % dpn == 0) else None

        fa_n = int(np.prod([mesh.shape[a] for a in fa]))
        tp_n = mesh.shape["model"]

        def wspec(w, f_axis):
            """Pytree spec for a (possibly quantized-dict) expert weight:
            experts on model, f dim FSDP'd, per-leaf divisibility-checked
            (stats tensors have size-1 dims).  Dequantizing *inside* the
            region guarantees the codes arrive as local slices (GSPMD drops
            the f-sharding through the int4-unpack reshape otherwise and
            replicates the dequantized tensor — measured, Perf log it2)."""
            def one(leaf):
                if leaf.ndim < 3:   # (k,) empirical codebook: replicated
                    return P(*([None] * leaf.ndim))
                dims = [None, None, None]
                if leaf.shape[0] % tp_n == 0:
                    dims[0] = "model"
                if leaf.shape[f_axis] % fa_n == 0:
                    dims[f_axis] = f_in
                return P(*dims)
            if is_qweight(w):
                return {k: one(v) for k, v in w.items()}
            return one(w)

        def region(hb, rw, g, u, dn):
            B_, S_, d_ = hb.shape
            idx = jax.lax.axis_index("model")
            cd = hb.dtype
            y = moe_lib.moe_ffn_local(
                hb.reshape(B_ * S_, d_), rw,
                materialize(g, cd), materialize(u, cd), materialize(dn, cd),
                mcfg, shard_idx=idx)
            return jax.lax.psum(y.reshape(B_, S_, d_), ("model",) + fa)

        return jax.shard_map(
            region, mesh=mesh,
            in_specs=(P(bspec, None, None), P(None, None),
                      wspec(eg, 2), wspec(eu, 2), wspec(ed, 1)),
            out_specs=P(bspec, None, None), check_vma=False,
        )(h, router_w, eg, eu, ed)

    dpn = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bspec = dp if (dp and B % dpn == 0) else None

    def region(hb, rw, g, u, dn):
        if fa:
            g = jax.lax.all_gather(g, fa, axis=1, tiled=True)
            u = jax.lax.all_gather(u, fa, axis=1, tiled=True)
            dn = jax.lax.all_gather(dn, fa, axis=2, tiled=True)
        return moe_lib.moe_ffn(hb, rw, g, u, dn, mcfg, axis_name="model")

    return jax.shard_map(
        region, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", f_in, None), P("model", f_in, None),
                  P("model", None, f_in)),
        out_specs=P(bspec, None, None), check_vma=False,
    )(h, router_w, eg, eu, ed)


def _ffn_block(x, lp, cfg: ArchConfig, opts: ModelOpts):
    h = _norm(x, lp["mlp_norm"], cfg)
    if cfg.is_moe:
        mcfg = moe_lib.MoEConfig(cfg.n_experts, cfg.top_k,
                                 cfg.capacity_factor)
        router_w = materialize(lp["router"], jnp.float32)
        if opts.moe_axis and opts.mesh is not None:
            if opts.moe_mode == "reduce":
                # pass raw (possibly quantized) weights; dequant in-region
                o = _moe_ep_sharded(h, router_w, lp["eg"], lp["eu"],
                                    lp["ed"], mcfg, opts)
            else:
                o = _moe_ep_sharded(h, router_w,
                                    materialize(lp["eg"], h.dtype),
                                    materialize(lp["eu"], h.dtype),
                                    materialize(lp["ed"], h.dtype),
                                    mcfg, opts)
        else:
            o = moe_lib.moe_ffn(h, router_w, materialize(lp["eg"], h.dtype),
                                materialize(lp["eu"], h.dtype),
                                materialize(lp["ed"], h.dtype), mcfg,
                                axis_name=None, act_fn=jax.nn.silu)
    else:
        act = cfg.mlp_act
        g = shard_act(mm_a(h, lp["w_gate"], opts), opts, "dp", None, "tp")
        u = shard_act(mm_a(h, lp["w_up"], opts), opts, "dp", None, "tp")
        g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
        o = mm_a(g * u, lp["w_down"], opts)
    o = shard_act(o, opts, "dp", None, None)
    if cfg.post_norms:
        o = _norm(o, lp["post_mlp_norm"], cfg)
    return o


def _maybe_quant_act(x, opts: ModelOpts):
    if opts.a_bits < 32:
        from repro.core.activations import fake_quant_act
        return fake_quant_act(x, opts.a_bits)
    return x


def decoder_layer(x, lp, cfg: ArchConfig, opts: ModelOpts, positions,
                  window):
    a, _ = _attn_block(x, lp, cfg, opts, positions, window)
    x = x + a
    x = x + _ffn_block(x, lp, cfg, opts)
    return _maybe_quant_act(x, opts)


# --------------------------------------------------------------------------
# Embedding / head / loss
# --------------------------------------------------------------------------

def _embed_tokens(params, cfg: ArchConfig, opts: ModelOpts, tokens):
    with jax.named_scope("embed"):
        emb = materialize(params["embed"], opts.compute_dtype)
        x = jnp.take(emb, tokens, axis=0)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return shard_act(x, opts, "dp", None, None)


def _head_weight(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        emb = params["embed"]
        if is_qweight(emb):
            # tied quantized embedding: dequantize then transpose
            return materialize(emb, jnp.bfloat16).T
        return emb.T
    return params["lm_head"]


def _seq_chunk(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (>=1)."""
    c = min(target, S)
    while S % c:
        c -= 1
    return c


def chunked_ce_loss(x, head_w, targets, cfg: ArchConfig, opts: ModelOpts):
    """Cross-entropy without materializing (B, S, V) logits.

    x (B, S, d), targets (B, S) int32 with -1 = ignore.  Scans over
    *sequence* chunks (batch stays sharded over the DP axes; logits stay
    sharded over `model` on V): peak logits memory = B_local * chunk * V /
    tp per device.
    """
    B, S, d = x.shape
    chunk = _seq_chunk(S, opts.ce_chunk)
    n = S // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, d), 1, 0)        # (n, B, c, d)
    tc = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)     # (n, B, c)

    def body(carry, inp):
        xb, tb = inp
        logits = jnp.dot(xb, materialize(head_w, xb.dtype),
                         preferred_element_type=jnp.float32)
        logits = shard_act(logits, opts, "dp", None, "tp")
        logits = softcap(logits, cfg.final_logit_cap)
        lse = jax.nn.logsumexp(logits, axis=-1)               # (B, c)
        gold = jnp.take_along_axis(
            logits, jnp.clip(tb, 0)[..., None], axis=-1)[..., 0]
        valid = (tb >= 0).astype(jnp.float32)
        loss = jnp.sum((lse - gold) * valid)
        return (carry[0] + loss, carry[1] + jnp.sum(valid)), None

    (total, count), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                     (xc, tc))
    return total / jnp.maximum(count, 1.0)


# --------------------------------------------------------------------------
# Forward passes
# --------------------------------------------------------------------------

_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "eg", "eu", "ed")


def _uniq_layer(lp, uniq_scan, layer_idx):
    """Apply the UNIQ transform to one layer's weights inside the scan.

    Per-layer transient + rematerialized in the backward pass — the
    whole-tree transform materializes a second copy of every parameter
    (catastrophic at 1T params); this keeps one layer live.  Per-tensor
    statistics match the stacked-tree semantics exactly (reduce over all
    non-leading axes).
    """
    if uniq_scan is None:
        return lp
    from repro.core.uniq import transform_param, _fold_path
    ucfg, modes, rng = uniq_scan
    mode = modes[layer_idx] if jnp.ndim(modes) else modes
    out = dict(lp)
    for key in _QUANT_KEYS:
        if key in lp and not is_qweight(lp[key]):
            krng = jax.random.fold_in(_fold_path(rng, key), layer_idx)
            out[key] = transform_param(lp[key], krng, mode, ucfg,
                                       stacked=False)
    return out


def _scoped(fn, name: str):
    """``fn`` with its whole body under the named scope ``name`` (the
    step programs' scope vocabulary: ``serve.engine.OP_SCOPES``)."""
    @functools.wraps(fn)
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return scoped


def _logits(params, cfg: ArchConfig, x):
    """f32 logits (B, V) of the final-normed rows ``x`` (B, d)."""
    with jax.named_scope("matmul"):
        logits = jnp.dot(x, materialize(_head_weight(params, cfg), x.dtype),
                         preferred_element_type=jnp.float32)
    return softcap(logits, cfg.final_logit_cap)


def _scan_layers(params, cfg: ArchConfig, opts: ModelOpts, x, positions,
                 collect_kv: bool = False, uniq_scan=None):
    windows = _window_schedule(cfg)
    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)

    def body(h, inp):
        lp, window, idx = inp
        lp = _uniq_layer(lp, uniq_scan, idx)
        if collect_kv:
            a, kv = _attn_block(h, lp, cfg, opts, positions, window,
                                kv_out=True)
            h = h + a
            h = h + _ffn_block(h, lp, cfg, opts)
            return _maybe_quant_act(h, opts), kv
        return decoder_layer(h, lp, cfg, opts, positions, window), None

    f = _scoped(body, "layer")
    if opts.remat:
        f = jax.checkpoint(body, prevent_cse=False)
    return jax.lax.scan(f, x, (params["layers"], windows, layer_ids))


def forward_train(params, cfg: ArchConfig, opts: ModelOpts, batch,
                  uniq_scan=None):
    """Teacher-forced LM loss.  batch: tokens/targets (+patch_embeds).

    ``uniq_scan = (UniqConfig, (L,) modes, rng)`` applies the UNIQ weight
    transform per layer inside the scan (see _uniq_layer)."""
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, opts, tokens)
    n_patches = 0
    if cfg.family == "vlm":
        pe = batch["patch_embeds"].astype(opts.compute_dtype)
        x = jnp.concatenate([pe, x], axis=1)
        n_patches = pe.shape[1]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, _ = _scan_layers(params, cfg, opts, x, positions,
                        uniq_scan=uniq_scan)
    x = _norm_final(x, params, cfg)
    if n_patches:
        x = x[:, n_patches:]
    return chunked_ce_loss(x, _head_weight(params, cfg), batch["targets"],
                           cfg, opts)


def _norm_final(x, params, cfg: ArchConfig):
    fn = params["final_norm"]
    if cfg.norm_kind == "layer":
        return layer_norm(x, fn["scale"], fn["bias"], cfg.norm_eps)
    return rms_norm(x, fn, cfg.norm_eps, zero_centered=cfg.post_norms)


def forward_prefill(params, cfg: ArchConfig, opts: ModelOpts, batch,
                    pad_to: Optional[int] = None,
                    last_idx: Optional[Array] = None):
    """Prefill: run the prompt, emit last-position logits + per-layer KV.

    Returns (logits (B, V), cache dict with k/v (L, B, S, KV, hd)).

    ``last_idx`` (B,) int32 selects a per-sequence "last" position instead
    of S-1 — the batched-prefill path for right-padded prompt groups (the
    logits at position i depend only on tokens <= i under the causal mask,
    so padding beyond last_idx is inert; see serve/engine.py).
    """
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, opts, tokens)
    if cfg.family == "vlm":
        pe = batch["patch_embeds"].astype(opts.compute_dtype)
        x = jnp.concatenate([pe, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, kvs = _scan_layers(params, cfg, opts, x, positions, collect_kv=True)
    with jax.named_scope("head"):
        x = _norm_final(x, params, cfg)
        last = x[:, -1] if last_idx is None \
            else x[jnp.arange(B), jnp.clip(last_idx, 0, S - 1)]
        logits = _logits(params, cfg, last)
    cache = kvs if isinstance(kvs, dict) else {"k": kvs[0], "v": kvs[1]}
    if pad_to and pad_to > S:
        # every cache leaf is (L, B, S, ...): pad the position axis
        cache = {name: jnp.pad(leaf, [(0, 0), (0, 0), (0, pad_to - S)]
                               + [(0, 0)] * (leaf.ndim - 3))
                 for name, leaf in cache.items()}
    return logits, cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Zeroed KV cache (L, B, S, KV, hd) for decoder-only families."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def init_paged_cache(cfg: ArchConfig, total_pages: int, page_size: int,
                     dtype=jnp.bfloat16, kv_bits: int = 16):
    """Zeroed paged KV pool, bit-width-parametric.

    kv_bits=16: dense {"k","v"} (L, total_pages, page_size, KV, hd).
    kv_bits=8/4: k-quantile codes {"k_codes","v_codes"} (int8, or uint8
    packed two-per-byte along hd for 4-bit) plus per-(row, head) bf16
    statistics {"k_mu","k_sigma","v_mu","v_sigma"} of shape
    (L, total_pages, page_size, KV) — see models/kv_cache.py.

    Page 0 is the reserved *sink*: never allocated to a sequence, it
    absorbs the writes of inactive decode rows and prefill right-padding
    (block-table entries default to 0), so scatters never need a mask.
    """
    kvq.check_kv_bits(kv_bits, cfg.head_dim)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    if kv_bits == 16:
        shape = (L, total_pages, page_size, KV, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    code_shape = (L, total_pages, page_size, KV,
                  hd // 2 if kv_bits == 4 else hd)
    code_dtype = packing.storage_dtype(kv_bits)
    stat_shape = (L, total_pages, page_size, KV)
    stats = {name: jnp.zeros(stat_shape, kvq.STATS_DTYPE)
             for name in ("k_mu", "k_sigma", "v_mu", "v_sigma")}
    return {"k_codes": jnp.zeros(code_shape, code_dtype),
            "v_codes": jnp.zeros(code_shape, code_dtype), **stats}


def cache_insert_paged(cache, prefill_cache, page_tables):
    """Scatter a prefill KV block into the paged pool (any kv_bits layout).

    cache         : pool pytree from ``init_paged_cache`` — dense
                    {"k","v"} (L, total_pages, page_size, ...) or the
                    codes+stats layout; every leaf has (total_pages,
                    page_size) as axes 1-2.
    prefill_cache : matching pytree of (L, G, S_pad, ...) leaves from a
                    padded batched prefill of G admitted prompts (codes
                    and stats scatter with the same page/row geometry as
                    dense rows — stats travel with their page).
    page_tables   : (G, n_pages) int32 destination page ids covering
                    [0, n_pages * page_size); entries past a prompt's
                    allocated pages (and whole pad rows) are 0 (sink).

    Rows past a prompt's true length hold right-padding garbage but land
    either in the sink page or in the tail of the sequence's last page,
    where decode's write-before-read (position t overwritten before the
    mask ``k_pos <= t`` exposes it) keeps them invisible — the same
    argument as the slot cache's padded insert.
    """
    ref_pool = next(iter(cache.values()))
    page = ref_pool.shape[2]
    n_pages = page_tables.shape[1]
    page_tables = jnp.asarray(page_tables, jnp.int32)

    def scatter(pool, kv):
        L, G, s_pad = kv.shape[:3]
        pad = n_pages * page - s_pad
        kv = jnp.pad(kv, [(0, 0), (0, 0), (0, pad)]
                     + [(0, 0)] * (kv.ndim - 3))
        kv = kv.reshape(L, G, n_pages, page, *kv.shape[3:])
        return pool.at[:, page_tables].set(kv.astype(pool.dtype))

    with jax.named_scope("kv_write"):
        return {name: scatter(cache[name], prefill_cache[name])
                for name in cache}


def _scan_pool(body, x, layers, windows, pool):
    """Run a step program's layer ``body`` over every layer with the KV
    pool in the scan's carry.

    ``body((h, pool), (lp, window, l)) -> (h, pool)`` writes layer ``l``'s
    fresh rows in place (``pool[name].at[l, ...]``) and reads its pages
    straight from the carried pool.  No layer's slice is restacked into a
    fresh buffer, so a donated pool is updated where it lies."""
    layer_ids = jnp.arange(windows.shape[0], dtype=jnp.int32)
    (x, pool), _ = jax.lax.scan(
        _scoped(lambda carry, inp: (body(carry, inp), None), "layer"),
        (x, pool), (layers, windows, layer_ids))
    return x, pool


def _write_kv(pool, l, index, k, v, kv_bits: int):
    """Write layer ``l``'s fresh K/V rows ``k``/``v`` (..., KV, hd) into
    the carried pool at ``[l, *index]``: dense ``k``/``v`` leaves, or, for
    a coded pool, each row's codes and statistics (models/kv_cache.py)."""
    with jax.named_scope("kv_write"):
        if kvq.is_quantized_cache(pool):
            k_st, k_mu, k_sig = kvq.quantize_kv(k, kv_bits)
            v_st, v_mu, v_sig = kvq.quantize_kv(v, kv_bits)
            rows = {"k_codes": k_st, "k_mu": k_mu, "k_sigma": k_sig,
                    "v_codes": v_st, "v_mu": v_mu, "v_sigma": v_sig}
        else:
            rows = {"k": k, "v": v}
        return {name: pool[name].at[(l, *index)].set(
                    rows[name].astype(pool[name].dtype)) for name in pool}


def prefill_chunk(params, cfg: ArchConfig, opts: ModelOpts, cache, tokens,
                  positions, write_pages, write_rows, block_tables,
                  last_idx):
    """Chunked prefill: run one sequence's next C prompt tokens against
    (and into) the paged pool (DESIGN.md Sec. 7).

    tokens       : (B, C) the chunks' token ids (right-padded; pad rows
                   compute garbage that lands in the sink).  B > 1 is the
                   *coalesced* path: one call advances several mid-prefill
                   sequences' chunks at once (serve/engine.py batches every
                   mid-prefill slot per step; pad rows beyond the live
                   group are all-sink no-ops).
    positions    : (C,) shared, or (B, C) per-sequence absolute positions
                   of the chunk rows (pad rows continue past the prompt).
    write_pages / write_rows : same shape as ``positions`` — pool
                   destination of each row's KV (page id and in-page row);
                   pad rows point at the sink page 0 (and shared pages
                   must have been copy-on-written by the scheduler before
                   the call).
    block_tables : (B, n_pages) each sequence's full block-table row.
    last_idx     : () or (B,) int32 index of each prompt's last token
                   *within the chunk* (meaningful on the final chunk — its
                   logits seed sampling exactly like whole-prefill's
                   ``last_idx``).

    Returns (logits (B, V) at ``last_idx``, updated pool).  Coalescing is
    bit-exact vs B=1 calls: a row's codes depend only on that row's K/V,
    sequences' block tables are disjoint (the shared sink page is only
    ever read under the causal mask, contributing exact zeros), and
    sampling folds by (seed, absolute position) — never batch shape.

    Each layer writes the chunk's fresh KV (codes + stats when
    ``opts.kv_bits < 16``) into the carried pool *before* attending, then
    attends over the gathered block-table row under the causal mask — the
    same write-before-read discipline as ``decode_step``, so a chunk sees
    earlier chunks' pages (including prefix-cache hits) plus its own rows,
    and produces bit-identical codes to a whole prefill of the same
    prompt: a row's codes depend only on that row's K/V, attention inputs
    match because masked rows contribute exact zeros, and the codec is
    shared (models/kv_cache.py).
    """
    B, C = tokens.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = _embed_tokens(params, cfg, opts, tokens)          # (B, C, d)
    positions = jnp.asarray(positions, jnp.int32)
    if positions.ndim == 1:                # legacy single-sequence layout
        positions = jnp.broadcast_to(positions[None], (B, C))
        write_pages = jnp.broadcast_to(
            jnp.asarray(write_pages, jnp.int32)[None], (B, C))
        write_rows = jnp.broadcast_to(
            jnp.asarray(write_rows, jnp.int32)[None], (B, C))
    else:
        write_pages = jnp.asarray(write_pages, jnp.int32)
        write_rows = jnp.asarray(write_rows, jnp.int32)
    last_idx = jnp.atleast_1d(jnp.asarray(last_idx, jnp.int32))
    pos2d = positions
    quant = kvq.is_quantized_cache(cache)

    def body(carry, inp):
        h, pool = carry
        lp, window, l = inp
        hn = _norm(h, lp["attn_norm"], cfg)
        q = mm_a(hn, lp["wq"], opts).reshape(B, C, H, hd)
        k = mm_a(hn, lp["wk"], opts).reshape(B, C, KV, hd)
        v = mm_a(hn, lp["wv"], opts).reshape(B, C, KV, hd)
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)
        p = attn.AttnParams(window=window, logit_cap=cfg.attn_logit_cap,
                            causal=True)
        pool = _write_kv(pool, l, (write_pages, write_rows), k, v,
                         opts.kv_bits)
        with jax.named_scope("attention"):
            if quant:
                o = attn.paged_prefill_attention_quant(
                    q, {n: a[l] for n, a in pool.items()}, block_tables,
                    pos2d, p, kv_bits=opts.kv_bits)
            else:
                o = attn.paged_prefill_attention(
                    q, pool["k"], pool["v"], l, block_tables, pos2d, p)
        o = mm_a(o.reshape(B, C, H * hd), lp["wo"], opts)
        if cfg.post_norms:
            o = _norm(o, lp["post_attn_norm"], cfg)
        h = h + o
        h = h + _ffn_block(h, lp, cfg, opts)
        return _maybe_quant_act(h, opts), pool

    x, cache_new = _scan_pool(body, x, params["layers"],
                              _window_schedule(cfg), dict(cache))
    with jax.named_scope("head"):
        x = _norm_final(x, params, cfg)
        last = x[jnp.arange(B), jnp.clip(last_idx, 0, C - 1)]  # (B, d)
        logits = _logits(params, cfg, last)
    return logits, cache_new


def decode_step(params, cfg: ArchConfig, opts: ModelOpts, cache, tokens,
                positions, block_tables=None):
    """One decode step.  tokens (B, 1); positions (B,) current index.

    cache: slot layout {"k","v"} (L, B, S, KV, hd) when ``block_tables``
    is None; paged layout (leaves (L, total_pages, page_size, ...),
    dense or k-quantile-coded — see ``init_paged_cache``) with
    ``block_tables`` (B, n_pages) int32 page indirection otherwise.

    Quantized pages (``opts.kv_bits < 16``): the step codes the fresh
    K/V row per (row, head), writes codes + stats into the pool, then
    attends through the fused gather+unpack+dequant paged path — the
    row's own code is written before it is read, matching prefill.

    The pool rides in the layer scan's carry (``_scan_pool``): each layer
    writes its B fresh rows in place and reads its pages from the carried
    pool, so a donated pool is updated where it lies.

    Returns (logits (B, V), updated cache).
    """
    B = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = _embed_tokens(params, cfg, opts, tokens)          # (B, 1, d)
    pos2d = positions[:, None]
    paged = block_tables is not None
    quant = kvq.is_quantized_cache(cache)
    if quant and not paged:
        raise ValueError("quantized KV cache requires the paged layout")
    if paged:
        page = next(iter(cache.values())).shape[2]
        with jax.named_scope("kv_write"):
            write_page = jnp.take_along_axis(
                block_tables, (positions // page)[:, None], axis=1)[:, 0]
            write_at = (write_page, positions % page)
    else:
        write_at = (jnp.arange(B), positions)

    def body(carry, inp):
        h, pool = carry
        lp, window, l = inp
        hn = _norm(h, lp["attn_norm"], cfg)
        q = mm_a(hn, lp["wq"], opts).reshape(B, 1, H, hd)
        k = mm_a(hn, lp["wk"], opts).reshape(B, 1, KV, hd)
        v = mm_a(hn, lp["wv"], opts).reshape(B, 1, KV, hd)
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)
        p = attn.AttnParams(window=window, logit_cap=cfg.attn_logit_cap,
                            causal=True)
        pool = _write_kv(pool, l, write_at, k[:, 0], v[:, 0], opts.kv_bits)
        with jax.named_scope("attention"):
            if quant:
                o = attn.paged_decode_attention_quant(
                    q, {n: a[l] for n, a in pool.items()}, block_tables,
                    positions, p, kv_bits=opts.kv_bits)
            elif paged:
                o = attn.paged_decode_attention(
                    q, pool["k"], pool["v"], l, block_tables, positions, p)
            else:
                o = attn.decode_attention(q, pool["k"][l], pool["v"][l],
                                          positions, p)
        o = mm_a(o.reshape(B, 1, H * hd), lp["wo"], opts)
        if cfg.post_norms:
            o = _norm(o, lp["post_attn_norm"], cfg)
        h = h + o
        h = h + _ffn_block(h, lp, cfg, opts)
        return _maybe_quant_act(h, opts), pool

    x, cache_new = _scan_pool(body, x, params["layers"],
                              _window_schedule(cfg), dict(cache))
    with jax.named_scope("head"):
        x = _norm_final(x, params, cfg)
        logits = _logits(params, cfg, x[:, 0])
    return logits, cache_new
