"""UNIQ orchestration: config, per-parameter transform, gradual schedule.

Three per-parameter modes (paper Sec. 3.3):

  CLEAN  (0) — parameter used as-is (blocks after the current stage).
  NOISE  (1) — uniform noise injection in the uniformized domain (the block
               currently being trained).
  FROZEN (2) — hard k-quantile quantization, stop-gradient, optimizer-masked
               (blocks already processed).

Modes are *traced* int32 values (per tensor, or per layer for scan-stacked
parameters), so advancing the gradual schedule never recompiles the step.

``transform_param`` is the pure-jnp reference; the Pallas kernel
(`repro.kernels.uniq_noise`) implements the same select in a single fused
VMEM pass and is validated against it.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import quantizers as Q
from repro.core.distributions import GaussianModel, fit_model
from repro.core.noise import inject, uniform_noise

Array = jax.Array

CLEAN, NOISE, FROZEN = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class UniqConfig:
    """Quantization hyper-parameters (paper Sec. 4 defaults)."""

    w_bits: int = 4                 # weight bits  -> k = 2**w_bits levels
    a_bits: int = 8                 # activation bits (32 = off)
    method: str = "kquantile"       # kquantile | uniform | kmeans
    dist: str = "gaussian"          # gaussian | empirical
    per_channel: bool = False       # beyond-paper: per-out-channel (mu, sigma)
    quantize_embed: bool = True     # paper quantizes first & last layers
    n_stages: int = 0               # 0 => one stage per block group
    stage_iterations: int = 2       # paper: two passes over the blocks
    enabled: bool = True

    @property
    def k(self) -> int:
        return 2 ** self.w_bits


def _stats_axes(w: Array, per_channel: bool, stacked: bool):
    """channel_axis argument for fit_model.

    stacked (L, ...) parameters always get at least per-layer statistics
    (axis 0 preserved); per_channel additionally preserves the trailing
    (output) axis.  Non-stacked: per_channel preserves the trailing axis.
    """
    if stacked:
        if per_channel and w.ndim >= 3:
            return (0, w.ndim - 1)
        return (0,)
    if per_channel and w.ndim >= 2:
        return (w.ndim - 1,)
    return None


def _fit_dist(w: Array, cfg: UniqConfig, stacked: bool):
    """Distribution model for the kquantile path, honoring ``cfg.dist``.

    Non-Gaussian dists apply per-tensor only (the sorted-sample ECDF has
    no per-channel form); per-channel / scan-stacked statistics stay
    Gaussian, the paper's model.
    """
    axes = _stats_axes(w, cfg.per_channel, stacked)
    if cfg.dist != "gaussian" and axes is None:
        return fit_model(w, cfg.dist)      # validates the kind
    if cfg.dist not in ("gaussian", "empirical"):
        raise ValueError(f"unknown distribution model: {cfg.dist!r}")
    return fit_gaussian(w, axes)


def fit_gaussian(w: Array, axes_keep) -> GaussianModel:
    """GaussianModel with statistics reduced over all axes not in axes_keep."""
    if axes_keep is None:
        return GaussianModel.fit(w)
    reduce_axes = tuple(a for a in range(w.ndim) if a not in axes_keep)
    mu = jnp.mean(w, axis=reduce_axes, keepdims=True)
    sigma = jnp.maximum(jnp.std(w, axis=reduce_axes, keepdims=True), 1e-8)
    return GaussianModel(mu=jax.lax.stop_gradient(mu),
                         sigma=jax.lax.stop_gradient(sigma))


def transform_param(w: Array, rng: Array, mode: Array, cfg: UniqConfig,
                    stacked: bool = False) -> Array:
    """Apply the 3-way UNIQ transform.  ``mode`` broadcasts against ``w``:
    scalar for plain params, (L,) (reshaped) for scan-stacked params.

    Single fused formulation: both NOISE and FROZEN paths share the forward
    CDF; the u-space perturbation is either additive uniform noise or
    snap-to-bin-center; CLEAN bypasses the transform entirely.
    """
    if not cfg.enabled or cfg.w_bits >= 32:
        return w
    k = cfg.k
    if cfg.method != "kquantile":
        # Ablation quantizers: per-bin noise amplitudes; handled by noise.py.
        noisy = inject(w, rng, k, method=cfg.method)
        frozen = jax.lax.stop_gradient(Q.fakequant(w, k, method=cfg.method))
        mode_b = _broadcast_mode(mode, w, stacked)
        return jnp.where(mode_b == CLEAN, w,
                         jnp.where(mode_b == NOISE, noisy, frozen))

    model = _fit_dist(w, cfg, stacked)
    u = model.cdf(w)
    e = uniform_noise(rng, w.shape, k, dtype=u.dtype)
    u_noise = jnp.clip(u + e, 1e-6, 1.0 - 1e-6)
    codes = jnp.clip(jnp.floor(u * k), 0, k - 1)
    u_frozen = (jax.lax.stop_gradient(codes) + 0.5) / k
    mode_b = _broadcast_mode(mode, w, stacked)
    u_sel = jnp.where(mode_b == NOISE, u_noise, u_frozen)
    w_hat = model.quantile(u_sel).astype(w.dtype)
    w_hat = jnp.where(mode_b == FROZEN, jax.lax.stop_gradient(w_hat), w_hat)
    return jnp.where(mode_b == CLEAN, w, w_hat)


def _broadcast_mode(mode: Array, w: Array, stacked: bool) -> Array:
    mode = jnp.asarray(mode)
    if stacked and mode.ndim == 1:
        return mode.reshape((mode.shape[0],) + (1,) * (w.ndim - 1))
    return mode


# --------------------------------------------------------------------------
# Parameter-tree application
# --------------------------------------------------------------------------

def default_quant_filter(path: str, leaf: Array) -> bool:
    """Which parameters get quantized: matmul-weight-like tensors.

    Excluded: norms/bias (1-D), router weights (routing stability), SSM
    A/dt/conv params (tiny + sensitive; see DESIGN.md Sec. 4).
    """
    lower = path.lower()
    if leaf.ndim < 2:
        return False
    if lower.split("/")[-1] == "d":   # mamba skip vector (L, nh)
        return False
    for token in ("norm", "router", "a_log", "dt_", "conv", "scale", "bias"):
        if token in lower:
            return False
    return True


def path_str(kp) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in kp)


def _fold_path(rng: Array, path: str) -> Array:
    h = int.from_bytes(hashlib.md5(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(rng, h)


def lm_mode_fn(layer_modes: Array):
    """Mode resolver for LM parameter trees with scan-stacked layers.

    Stacked leaves (path under ``layers``) get the full (L,) vector; the
    embedding belongs to the first gradual block and the LM head to the last
    (the paper quantizes first and last layers too).
    """
    def mode_for(path: str):
        if path.startswith("layers"):
            return layer_modes
        if "embed" in path:
            return layer_modes[0]
        return layer_modes[-1]
    return mode_for


def transform_tree(params: Any, rng: Array, modes: Any, cfg: UniqConfig,
                   quant_filter: Callable[[str, Array], bool] | None = None,
                   stacked_prefixes: tuple = ("layers",)) -> Any:
    """Apply UNIQ to a parameter pytree.

    ``modes``: scalar mode applied to every quantized leaf, or a callable
    ``path -> mode`` (see ``lm_mode_fn``).  Leaves whose path starts with one
    of ``stacked_prefixes`` are treated as scan-stacked (leading layer axis)
    and may receive an (L,) per-layer mode vector.
    """
    quant_filter = quant_filter or default_quant_filter
    mode_for = modes if callable(modes) else (lambda _p: modes)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for kp, leaf in flat:
        p = path_str(kp)
        if not quant_filter(p, leaf):
            out.append(leaf)
            continue
        if not cfg.quantize_embed and ("embed" in p or "head" in p):
            out.append(leaf)
            continue
        stacked = any(p.startswith(pre) for pre in stacked_prefixes)
        leaf_mode = jnp.asarray(mode_for(p))
        out.append(transform_param(leaf, _fold_path(rng, p), leaf_mode, cfg,
                                   stacked=stacked))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# Gradual quantization schedule (paper Sec. 3.3, App. B)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GradualSchedule:
    """Maps training step -> per-layer modes.

    ``n_layers`` layers are grouped into ``n_blocks`` contiguous blocks; the
    budget of ``total_steps`` is split into ``n_blocks * iterations`` stages.
    At stage s (within an iteration): blocks < s are FROZEN, block s gets
    NOISE, blocks > s are CLEAN — except in iterations > 0 where already-
    visited blocks stay FROZEN (paper: restart from the beginning so earlier
    blocks adapt; we keep earlier blocks frozen and re-noise the active one).
    After all stages everything is FROZEN (pure quantized fine-tune of norms
    and biases continues).
    """

    n_layers: int
    n_blocks: int
    total_steps: int
    iterations: int = 2

    def __post_init__(self):
        if self.n_layers < 1:
            raise ValueError(f"n_layers must be >= 1, got {self.n_layers}")
        if self.n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {self.n_blocks}")
        # n_blocks > n_layers would leave blocks with no layer: their stages
        # run with zero NOISE layers and silently burn step budget.
        if self.n_blocks > self.n_layers:
            object.__setattr__(self, "n_blocks", self.n_layers)

    @property
    def n_stages(self) -> int:
        return self.n_blocks * self.iterations

    @property
    def steps_per_stage(self) -> int:
        return max(1, self.total_steps // max(self.n_stages, 1))

    def block_of_layer(self) -> jnp.ndarray:
        idx = jnp.arange(self.n_layers)
        return (idx * self.n_blocks) // max(self.n_layers, 1)

    def modes_at(self, step) -> jnp.ndarray:
        """(n_layers,) int32 modes for ``step`` (host int or traced)."""
        step = jnp.asarray(step)
        stage = jnp.minimum(step // self.steps_per_stage, self.n_stages)
        active_block = stage % self.n_blocks
        iteration = stage // self.n_blocks
        blocks = self.block_of_layer()
        done_all = stage >= self.n_stages
        first_iter = iteration == 0
        frozen = jnp.where(first_iter, blocks < active_block,
                           blocks != active_block)
        active = blocks == active_block
        modes = jnp.where(active, NOISE,
                          jnp.where(frozen, FROZEN, CLEAN))
        return jnp.where(done_all, FROZEN, modes).astype(jnp.int32)

    def freeze_mask_at(self, step) -> jnp.ndarray:
        """(n_layers,) bool — True where the optimizer may update."""
        return self.modes_at(step) != FROZEN


# --------------------------------------------------------------------------
# Quantized parameter container (serving path)
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Packed k-quantile codes + Gaussian statistics; dequantizes analytically.

    codes: uint8 (int4 packed 2/byte along last axis) or int8 (8-bit).
    mu, sigma: broadcastable statistics (per-tensor or per-channel).
    """

    codes: Array
    mu: Array
    sigma: Array
    bits: int
    shape: tuple

    def tree_flatten(self):
        return (self.codes, self.mu, self.sigma), (self.bits, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, mu, sigma = children
        bits, shape = aux
        return cls(codes, mu, sigma, bits, shape)

    @property
    def k(self) -> int:
        return 2 ** self.bits

    def dequantize(self, dtype=jnp.bfloat16) -> Array:
        from repro.core import packing
        codes = self.codes
        if self.bits == 4:
            codes = packing.unpack_int4(codes)
        c = codes.astype(jnp.float32) + (128.0 if self.k == 256 else 0.0)
        centers = (c + 0.5) / self.k
        from jax.scipy.special import ndtri
        centers = jnp.clip(centers, 1e-6, 1 - 1e-6)
        w = self.mu + self.sigma * ndtri(centers)
        return w.reshape(self.shape).astype(dtype)


def quantize_tensor(w: Array, bits: int, per_channel: bool = True,
                    stacked: bool = False) -> QuantizedTensor:
    """Offline k-quantile quantization of a weight tensor for serving.

    Codes go straight to their uint8/int8 storage; no int32 copy of the
    tensor is made."""
    from repro.core import packing
    model = fit_gaussian(w, _stats_axes(w, per_channel, stacked))
    codes = Q.kquantile_quantize(w, model, 2 ** bits, code_dtype=jnp.uint8)
    stored = packing.store_codes(codes, bits)
    return QuantizedTensor(stored, model.mu.astype(jnp.float32),
                           model.sigma.astype(jnp.float32), bits,
                           tuple(w.shape))


def quantize_tree(params: Any, bits: int,
                  quant_filter: Callable[[str, Array], bool] | None = None,
                  per_channel: bool = True,
                  stacked_prefixes: tuple = ("layers",)) -> Any:
    """Quantize every eligible leaf of a parameter tree for serving."""
    quant_filter = quant_filter or default_quant_filter
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for kp, leaf in flat:
        p = path_str(kp)
        if quant_filter(p, leaf):
            stacked = any(p.startswith(pre) for pre in stacked_prefixes)
            out.append(quantize_tensor(leaf, bits, per_channel=per_channel,
                                       stacked=stacked))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)
