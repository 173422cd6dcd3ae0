"""The served-weight build, the compile-cache rule and chip_smoke.py's
device check."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import lm, model

REPO = pathlib.Path(__file__).resolve().parents[1]


def _assert_trees_bit_identical(a, b):
    fa, ta = jax.tree_util.tree_flatten_with_path(a)
    fb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb
    for (path, x), (_, y) in zip(fa, fb):
        assert x.dtype == y.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


def _dense_as_served(tree):
    """``quantize_for_serving``'s tree with the dense matmul weights it
    keeps in f32 cast the way the streamed build serves them."""
    from repro.core.uniq import default_quant_filter, path_str

    def one(kp, w):
        if lm.is_qweight(w) or not default_quant_filter(path_str(kp), w):
            return w
        return w.astype(lm.SERVE_DENSE_DTYPE)
    return jax.tree_util.tree_map_with_path(one, tree, is_leaf=lm.is_qweight)


@pytest.mark.parametrize("bits,dist", [(4, "gaussian"), (8, "gaussian"),
                                       (4, "empirical")])
def test_streamed_build_matches_whole_tree_coding(bits, dist):
    """Layer-by-layer build == coding model.init's whole float tree, bit
    for bit: the same weights (one random draw) and the same per-layer
    coder."""
    cfg = cb.get_smoke("granite_3_8b")
    rng = jax.random.PRNGKey(7)
    whole = model.quantize_for_serving(model.init(rng, cfg), bits, dist)
    streamed = model.build_for_serving(rng, cfg, bits, dist)
    assert lm.is_qweight(streamed["layers"]["w_gate"])
    _assert_trees_bit_identical(streamed, _dense_as_served(whole))


def test_streamed_build_w16_is_bf16():
    """At W16 the matmul weights are served in bf16 and norm scales stay
    f32: the served path no longer runs on an f32 tree."""
    cfg = cb.get_smoke("granite_3_8b")
    rng = jax.random.PRNGKey(7)
    ref = model.init(rng, cfg)
    got = model.build_for_serving(rng, cfg, 16)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert got["layers"][name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got["layers"][name]),
            np.asarray(ref["layers"][name].astype(jnp.bfloat16)))
    assert got["embed"].dtype == jnp.bfloat16
    assert got["layers"]["attn_norm"].dtype == jnp.float32
    assert got["final_norm"].dtype == jnp.float32
    _assert_trees_bit_identical(got, _dense_as_served(ref))


def test_streamed_build_refuses_state_space_families():
    with pytest.raises(ValueError, match="no streamed serving build"):
        model.build_for_serving(jax.random.PRNGKey(0),
                                cb.get_smoke("mamba2_1_3b"), 4)


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


class _CpuDevice:
    platform = "cpu"
    device_kind = "cpu"


def test_chip_smoke_device_check_refuses_cpu(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_CpuDevice()])
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.check_device()
