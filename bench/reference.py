"""Plain float32 reference of the served dense decoder, and the comparison.

It imports nothing of the program and takes nothing the program made.  It
draws the weights from the seed by the recipe the configuration file states
(``weights`` in ``bench/configs/<config>.json``), puts them in the stated
served format (4-bit k-quantile codes per output column, or bf16), and runs
the forward pass in float32 at ``highest`` matmul precision, one layer at a
time over every checked sequence, so that only one layer's weights are live.
K and V are coded as the stated pool holds them: k-quantile codes per (row,
head) with bf16 statistics at kv8/kv4, bf16 rows at kv16.

The layer equations are the program's, which depart from the published
Granite model where the configuration's ``departures`` say so: no
embedding, attention, residual or logit multipliers, an untied head, RMS
epsilon 1e-6.

The comparison: the engine returns token ids, not logits, so the reference
reaches the logits by running each checked prompt with its served tokens and
reading, at every served position, by how much the served token's logit
lies below the reference's best (``gap``).  Greedy decoding in exact
arithmetic gives 0 everywhere; rounding gives small gaps where the top two
logits nearly tie.  The control runs further streams whose linear layers
take their weights (per output column) and activations (per token) in a
lower precision, int8 or fp8 (e4m3), and reads, at the same positions, the
gap of the token each stream puts first.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtr

_EPS = 1e-6                      # clip of the k-quantile CDF
_SQRT2 = math.sqrt(2.0)
_BUCKETS = (1024, 2048, 4096)    # sequence lengths are padded to one


def _level(codes: jax.Array, k: int) -> jax.Array:
    """Standardized level of each code, Phi^-1((c + 1/2) / k), computed
    per element (a table lookup is a slow gather on the TPU)."""
    centers = jnp.clip((codes.astype(jnp.float32) + 0.5) / k, _EPS, 1 - _EPS)
    return _SQRT2 * jax.lax.erf_inv(2.0 * centers - 1.0)


def kquantile_roundtrip(w: jax.Array, bits: int, axis: int) -> jax.Array:
    """Code ``w`` to ``bits``-bit k-quantile levels of a Gaussian fitted
    along ``axis`` (per slice of the other axes), and decode it."""
    k = 2 ** bits
    mu = jnp.mean(w, axis=axis, keepdims=True)
    sigma = jnp.maximum(jnp.std(w, axis=axis, keepdims=True), 1e-8)
    u = jnp.clip(ndtr((w - mu) / sigma), _EPS, 1 - _EPS)
    codes = jnp.clip(jnp.floor(u * k), 0, k - 1)
    return mu + sigma * _level(codes, k)


def kv_roundtrip(x: jax.Array, bits: int) -> jax.Array:
    """K or V rows (..., hd) as the pool stores them."""
    if bits >= 16:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    k = 2 ** bits
    mu = jnp.mean(x, -1, keepdims=True).astype(jnp.bfloat16).astype(
        jnp.float32)
    sigma = jnp.maximum(jnp.std(x, -1, keepdims=True), 1e-8).astype(
        jnp.bfloat16).astype(jnp.float32)
    u = jnp.clip(0.5 * (1 + jax.lax.erf((x - mu) / sigma / _SQRT2)),
                 _EPS, 1 - _EPS)
    codes = jnp.clip(jnp.floor(u * k), 0, k - 1)
    return mu + sigma * _level(codes, k)


CONTROLS = ("int8", "fp8")


def low_rows(x: jax.Array, mode: str) -> jax.Array:
    """``x`` rounded per row (scaled to its absmax over the last axis) to
    int8 or to fp8 e4m3, and decoded."""
    top = 127.0 if mode == "int8" else 448.0
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    if mode == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def low_columns(w: jax.Array, mode: str) -> jax.Array:
    return low_rows(w.T, mode).T


def _trunc(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def served(w: jax.Array, w_bits: int, axis: int = 0) -> jax.Array:
    """A matmul weight in its stated served format, as f32 values."""
    if w_bits >= 16 or (w_bits == 4 and w.shape[-1] % 2):
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    return kquantile_roundtrip(w, w_bits, axis)


class Model:
    """The configuration's weights, drawn and put in served form on
    demand, layer by layer."""

    def __init__(self, c: dict, seed: int, control: bool = False):
        self.c = c
        self.controls = CONTROLS if control else ()
        self.L, self.d = c["num_hidden_layers"], c["hidden_size"]
        self.f, self.V = c["intermediate_size"], c["vocab_size"]
        self.H, self.KV = c["num_attention_heads"], c["num_key_value_heads"]
        self.hd = c["head_dim"]
        self.w_bits = c["serving"]["w_bits"]
        self.kv_bits = c["serving"]["kv_bits"]
        self.eps = c["rms_norm_eps"]
        self.theta = c["rope_theta"]
        self.keys = jax.random.split(jax.random.PRNGKey(seed), 16)
        self.layer_keys = jax.random.split(self.keys[10], self.L)

    # weights: the recipe of the configuration's "weights" entry
    def layer(self, i: int) -> Dict[str, jax.Array]:
        return _layer_weights(self.layer_keys[i], self.d, self.f, self.H,
                              self.KV, self.hd, self.w_bits, self.controls)

    def top(self) -> Dict[str, jax.Array]:
        return _top_weights(self.keys[8], self.keys[9], self.d, self.V,
                            self.w_bits, self.controls)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _layer_weights(key, d, f, H, KV, hd, w_bits, controls):
    ks = jax.random.split(key, 8)
    shapes = {"wq": (0, (d, H * hd)), "wk": (1, (d, KV * hd)),
              "wv": (2, (d, KV * hd)), "wo": (3, (H * hd, d)),
              "w_gate": (5, (d, f)), "w_up": (6, (d, f)),
              "w_down": (7, (f, d))}
    out = {}
    for name, (i, shape) in shapes.items():
        w = served(_trunc(ks[i], shape, (1.0 / shape[0]) ** 0.5), w_bits)
        out[name] = w
        for mode in controls:
            out[f"{name}_{mode}"] = low_columns(w, mode)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _top_weights(k_embed, k_head, d, V, w_bits, controls):
    embed = served(_trunc(k_embed, (V, d), 0.02), w_bits)
    head = served(_trunc(k_head, (d, V), (1.0 / d) ** 0.5), w_bits)
    out = {"embed": embed, "head": head}
    for mode in controls:
        out[f"head_{mode}"] = low_columns(head, mode)
    return out


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (S, n, hd); rotate-half RoPE."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 9))
def _layer(x, w, H, KV, hd, kv_bits, eps, theta, n_valid, mode=None):
    """One layer over a padded sequence; ``mode`` runs its linear layers
    in a control's precision."""
    S = x.shape[0]
    sfx = f"_{mode}" if mode else ""

    def _linear(a, name):
        return (low_rows(a, mode) if mode else a) @ w[name + sfx]
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _rms(x, eps)
    q = _linear(h, "wq").reshape(S, H, hd)
    k = _linear(h, "wk").reshape(S, KV, hd)
    v = _linear(h, "wv").reshape(S, KV, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k, v = kv_roundtrip(k, kv_bits), kv_roundtrip(v, kv_bits)
    G = H // KV
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(S, KV, G, hd), k) / hd ** 0.5
    mask = (pos[:, None] >= pos[None, :]) & (pos[None, :] < n_valid)
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", a, v).reshape(S, H * hd)
    x = x + _linear(o, "wo")
    h = _rms(x, eps)
    g = _linear(h, "w_gate")
    u = _linear(h, "w_up")
    return x + _linear(jax.nn.silu(g) * u, "w_down")


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(x, head, targets, eps, mode=None):
    """(best logit, logit of target, argmax) at every position."""
    lg = (low_rows(_rms(x, eps), mode) if mode else _rms(x, eps)) @ head
    tgt = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, -1), tgt, jnp.argmax(lg, -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _logits_at(x, head, eps, idx):
    lg = _rms(x, eps) @ head
    return jnp.take_along_axis(lg, idx[:, None], axis=1)[:, 0]


def gaps(c: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int]],
         control: bool = False) -> Dict[str, object]:
    """Served-token gaps of ``seqs`` = [(prompt + served tokens, n_prompt)].

    Returns, over every served token, ``max_gap`` (the widest gap) and
    ``mean_gap``; ``tokens`` (served tokens compared); ``per_seq`` maxima;
    and with ``control``, ``control``: per control precision, the same two
    numbers for the token that stream puts first at each position."""
    with jax.default_matmul_precision("highest"):
        return _gaps(c, seed, seqs, control)


def _gaps(c, seed, seqs, control):
    m = Model(c, seed, control)
    top = m.top()
    lens = [len(t) for t, _ in seqs]
    pads = [next((b for b in _BUCKETS if b >= n), -(-n // 1024) * 1024)
            for n in lens]
    toks = [jnp.asarray(np.pad(np.asarray(t, np.int32), (0, p - n)))
            for (t, _), n, p in zip(seqs, lens, pads)]
    streams = {None: [top["embed"][t] for t in toks]}
    for mode in m.controls:
        streams[mode] = list(streams[None])
    heads = {k: v for k, v in top.items() if k != "embed"}
    del top
    for i in range(m.L):
        w = m.layer(i)
        for mode, xs in streams.items():
            streams[mode] = [_layer(x, w, m.H, m.KV, m.hd, m.kv_bits, m.eps,
                                    m.theta, n, mode)
                             for x, n in zip(xs, lens)]
        del w
    out: Dict[str, object] = {"per_seq": [], "tokens": 0, "max_gap": 0.0}
    total = 0.0
    ctl = {mode: {"max_gap": 0.0, "sum": 0.0} for mode in m.controls}
    for j, ((t, n_prompt), n) in enumerate(zip(seqs, lens)):
        tgt = np.zeros(pads[j], np.int32)
        tgt[:n - 1] = np.asarray(t[1:], np.int32)
        best, got, _ = _logits(streams[None][j], heads["head"],
                               jnp.asarray(tgt), m.eps)
        sl = slice(n_prompt - 1, n - 1)        # positions of served tokens
        best = np.asarray(best)[sl]
        g = best - np.asarray(got)[sl]
        out["per_seq"].append(float(g.max()))
        out["tokens"] += int(g.size)
        out["max_gap"] = max(out["max_gap"], float(g.max()))
        total += float(g.sum())
        for mode in m.controls:
            _, _, pick = _logits(streams[mode][j], heads[f"head_{mode}"],
                                 jnp.asarray(tgt), m.eps, mode)
            at = _logits_at(streams[None][j], heads["head"], m.eps, pick)
            gc = best - np.asarray(at)[sl]
            ctl[mode]["max_gap"] = max(ctl[mode]["max_gap"], float(gc.max()))
            ctl[mode]["sum"] += float(gc.sum())
    out["mean_gap"] = total / max(out["tokens"], 1)
    if control:
        out["control"] = {mode: {"max_gap": c["max_gap"],
                                 "mean_gap": c["sum"] / max(out["tokens"], 1)}
                          for mode, c in ctl.items()}
    return out
