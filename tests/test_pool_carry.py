"""The step programs carry the KV pool through the layer scan.

``decode_step`` and ``prefill_chunk`` run their layers through
``lm._scan_pool``: the pool rides in the scan's carry, each layer writes its
fresh rows in place at its layer index and reads its pages from there.  The
formulation it replaces took the pool as the scan's xs, one layer's slice per
iteration, and stacked the updated slices back as ys.  Both must give the
same logits and the same pool, bit for bit, in every layout.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import lm, model
from repro.models.lm import ModelOpts

PAGE, N_PAGES, TOTAL_PAGES, SLOT_LEN = 4, 3, 12, 16


def _xs_ys_scan(body, x, layers, windows, pool):
    """The replaced formulation: layer l's slice of the pool goes in as the
    scan's xs and comes back stacked as ys (``body`` sees it as a one-layer
    pool at layer 0)."""
    def step(h, inp):
        lp, window, kc = inp
        h, kc = body((h, {n: a[None] for n, a in kc.items()}),
                     (lp, window, jnp.int32(0)))
        return h, {n: a[0] for n, a in kc.items()}
    return jax.lax.scan(step, x, (layers, windows, pool))


def _filled(pool, seed):
    """``pool`` with every leaf drawn at random (positive sigmas), so a
    read or write at the wrong layer or page shows."""
    out = {}
    for i, (name, a) in enumerate(sorted(pool.items())):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        if jnp.issubdtype(a.dtype, jnp.integer):
            x = jax.random.randint(key, a.shape, 0, 127)
        elif name.endswith("sigma"):
            x = 0.5 + jax.random.uniform(key, a.shape)
        else:
            x = jax.random.normal(key, a.shape)
        out[name] = x.astype(a.dtype)
    return out


def _programs(params, cfg, opts, pool, B, paged):
    """(name, program, arguments) of each step program the layout runs:
    decode at B rows, and for a paged pool a B-row chunk."""
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, 1), 0, cfg.vocab)
    pos = jnp.arange(B, dtype=jnp.int32) * 2 + 5
    if not paged:
        return [("decode", lambda c, t, p: lm.decode_step(
            params, cfg, opts, c, t, p), (pool, toks, pos))]
    bt = (jnp.arange(B * N_PAGES, dtype=jnp.int32) + 1).reshape(B, N_PAGES)
    C = PAGE
    cpos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32) + PAGE, (B, C))
    chunk_args = (pool,
                  jax.random.randint(jax.random.PRNGKey(8), (B, C), 0,
                                     cfg.vocab),
                  cpos, jnp.take_along_axis(bt, cpos // PAGE, axis=1),
                  cpos % PAGE, bt, jnp.full((B,), C - 1, jnp.int32))
    return [("decode", lambda c, t, p, b: lm.decode_step(
                params, cfg, opts, c, t, p, b), (pool, toks, pos, bt)),
            ("chunk", lambda c, *a: lm.prefill_chunk(
                params, cfg, opts, c, *a), chunk_args)]


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("layout", ["kv16", "kv8", "kv4", "slot"])
def test_pool_carry_matches_xs_ys_scan(layout, B, monkeypatch):
    cfg = dataclasses.replace(cb.get_smoke("granite_3_8b"), n_layers=3)
    params = model.init(jax.random.PRNGKey(0), cfg)
    kv_bits = 16 if layout == "slot" else int(layout[2:])
    opts = ModelOpts(compute_dtype=jnp.bfloat16, remat=False,
                     attn_chunked_min_len=1 << 30, kv_bits=kv_bits)
    if layout == "slot":                 # (L, B, S, KV, hd), one row each
        shape = (cfg.n_layers, B, SLOT_LEN, cfg.n_kv_heads, cfg.head_dim)
        pool = {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16)}
    else:
        pool = lm.init_paged_cache(cfg, TOTAL_PAGES, PAGE, jnp.bfloat16,
                                   kv_bits=kv_bits)
    pool = _filled(pool, kv_bits)

    paged = layout != "slot"
    for name, fn, args in _programs(params, cfg, opts, pool, B, paged):
        logits, new_pool = jax.jit(fn)(*args)
        with monkeypatch.context() as m:
            m.setattr(lm, "_scan_pool", _xs_ys_scan)
            # a new function, so jit traces it afresh under the patch
            ref_logits, ref_pool = jax.jit(lambda *a: fn(*a))(*args)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits), err_msg=name)
        assert set(new_pool) == set(ref_pool) == set(pool)
        for leaf in pool:
            np.testing.assert_array_equal(
                np.asarray(new_pool[leaf]), np.asarray(ref_pool[leaf]),
                err_msg=f"{name}: {leaf}")
        # the step wrote something: the comparison is not of two no-ops
        assert any(not np.array_equal(np.asarray(new_pool[leaf]),
                                      np.asarray(pool[leaf]))
                   for leaf in pool), name
