"""Smoke run of the serving engine on one TPU chip at published width.

    python chip_smoke.py [--seed N]

Serves granite_3_8b (40 layers, d_model 4096, 32 heads / 8 KV heads,
d_ff 12800, vocab 49155) from random weights made from ``--seed``,
through the normal entry points: ``repro.launch.serve`` builds the
served tree and drives ``serve.engine.Engine`` with its request stream.

Phases, in order; any failure exits nonzero before the last line:

  device        the first JAX device must be a TPU (never the CPU)
  build         W4 k-quantile tree built layer by layer on the device
  w4_kv8        8 requests (256-token shared prefix + 256..512 own tokens,
                32 new tokens each) with the prefix cache and whole-prompt
                chunked prefill; the first request registers the shared
                prefix, later ones must hit it.  The shared prefix is
                page-aligned, so the stream makes no copy-on-write; one
                more prompt, served twice, must make one
  w4_kv4        the same requests on a fresh engine with a kv4 pool and
                whole-prompt batched prefill
  w16_kv16_cut  bf16 weights and a kv16 pool; depth cut to 20 layers
                (40 layers of bf16 weights, 16.7 GB, exceed 16 GiB HBM)

After each quantized-KV phase the Pallas paged-attention kernel is
compared with the jnp reference on one layer of the pool the engine
filled, and the engine's compiled decode program must hold the kernel
(``tpu_custom_call``).  The tok/s printed are smoke figures, not a
benchmark.  The last line of stdout is one JSON object naming the device.

One process holds the chip; nothing else is started.  JAX's compile cache
follows ``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "granite_3_8b"
W16_LAYERS = 20
COW_PROMPT_LEN = 300
# the request stream of every phase: --rate 50 puts all 8 arrivals within
# ~0.2 s, while serving them takes seconds, so the queue fills at once
STREAM = ["--arch", ARCH, "--engine", "--requests", "8", "--rate", "50",
          "--prompt-len", "512", "--shared-prefix", "256",
          "--new-tokens", "32", "--max-slots", "8", "--max-len", "1024",
          "--page-size", "64"]
PHASES = {
    # --prefill-chunk 12 pages covers the longest prompt (768 tokens), so
    # the first request's prefill completes, and registers the shared
    # prefix, within the step that admits it
    "w4_kv8": ["--w-bits", "4", "--kv-bits", "8", "--prefix-cache",
               "--prefill-chunk", "12"],
    "w4_kv4": ["--w-bits", "4", "--kv-bits", "4"],
    "w16_kv16_cut": ["--w-bits", "16", "--kv-bits", "16"],
}
# Kernel vs. reference: the kernel dequantizes K/V and runs the softmax
# and both dots in f32; the reference (the engine's off-TPU path) rounds
# the dequantized K and V and the softmax weights to bf16, whose 8
# significant bits bound each rounding by 2**-8 relative.  Both round the
# output to bf16, so two nearly equal outputs can land one bf16 step
# apart: 2**-7 of |out|, and |out| <= max |V| since the output is a convex
# combination of V rows.  The tolerance is two such steps at max |V|.
KERNEL_TOL_OF_MAX_V = 2.0 ** -6

class CompileLog:
    """Seconds of every XLA backend compile (or compile-cache load) the
    process makes from now on, in order."""

    def __init__(self):
        self.secs: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs.append(secs)


def require(ok: bool, what: str) -> None:
    """A failed check ends the run (not an assert: ``python -O`` keeps it)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def check_device():
    """The first JAX device; raises unless it is a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU; JAX's first device is "
            f"{dev.platform} ({dev.device_kind}). It never falls back to "
            f"the CPU.")
    return dev


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def kernel_vs_reference(eng, kv_bits: int, seed: int) -> float:
    """Largest |kernel - reference| of one layer's decode attention over
    the pool the engine filled, against its tolerance; returns it."""
    from repro.models import attention as attn
    from repro.models import kv_cache as kvq
    cfg, page = eng.cfg, eng.ec.page_size
    layer = cfg.n_layers // 2
    pool = {name: leaf[layer] for name, leaf in eng.kv_pool.items()}
    n_pages, B = 16, eng.ec.max_slots
    P = pool["k_mu"].shape[0]
    # every non-sink page of the pool, sequence by sequence
    tables = jnp.asarray(1 + np.arange(B * n_pages) % (P - 1),
                         jnp.int32).reshape(B, n_pages)
    q_pos = jnp.full((B,), n_pages * page - 1, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (B, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    p = attn.AttnParams(window=None, logit_cap=cfg.attn_logit_cap,
                        causal=True)
    outs = [jax.block_until_ready(attn.paged_decode_attention_quant(
        q, pool, tables, q_pos, p, kv_bits=kv_bits, use_pallas=use))
        for use in (True, False)]
    kern, ref = (np.asarray(o, np.float32) for o in outs)
    v = kvq.dequantize_kv(pool["v_codes"][tables], pool["v_mu"][tables],
                          pool["v_sigma"][tables], kv_bits)
    max_v = float(jnp.max(jnp.abs(v)))
    err = float(np.max(np.abs(kern - ref)))
    tol = KERNEL_TOL_OF_MAX_V * max_v
    print(f"[check] kv{kv_bits} layer {layer}: kernel vs reference max "
          f"|diff| {err:.6g} (tolerance {tol:.6g} = 2**-6 x max|V| "
          f"{max_v:.6g}); kernel output finite: "
          f"{bool(np.isfinite(kern).all())}", flush=True)
    require(np.isfinite(kern).all() and np.isfinite(ref).all(),
            "finite attention outputs")
    require(err <= tol, f"kernel within {tol} of the reference ({err})")
    return err


def copy_on_write(eng, seed: int) -> None:
    """Serve one prompt twice through the prefix cache.  The second
    admission hits every token but the last, whose KV row lands mid-page
    in a page the cache shares, so the engine must copy that page first."""
    from repro.serve.engine import Request, SamplingParams
    cow0 = eng.stats()["cow_copies"]
    # 299 cached tokens end 43 rows into the fifth 64-token page
    prompt = np.random.default_rng(seed).integers(
        0, eng.cfg.vocab, size=COW_PROMPT_LEN).astype(np.int32)
    outs = [eng.generate([Request(uid=1000 + i, prompt=prompt.copy(),
                                  sampling=SamplingParams(max_new_tokens=8))])
            for i in range(2)]
    cow = eng.stats()["cow_copies"] - cow0
    tokens = [o[0].token_ids for o in outs]
    print(f"[copy_on_write] a {COW_PROMPT_LEN}-token prompt served twice: "
          f"{cow} copy-on-writes; greedy tokens of the cached admission "
          f"equal the first's: {list(tokens[0]) == list(tokens[1])}",
          flush=True)
    require(all(len(t) == 8 for t in tokens), "8 new tokens per admission")
    require(cow > 0, "a copy-on-write")


def run_phase(name: str, params, cfg, seed: int, dev,
              compile_log: CompileLog) -> dict:
    """One engine stream through launch/serve.py; checks every request
    got its full token count and the pool stayed finite."""
    from repro.launch import serve
    from repro.serve.engine import Engine
    args = serve.build_parser().parse_args(
        STREAM + PHASES[name] + ["--seed", str(seed)])
    print(f"[{name}] {' '.join(PHASES[name])}", flush=True)
    eng = Engine(params, cfg, serve.SERVE_OPTS, serve.engine_config(args))
    t0, n0 = time.perf_counter(), len(compile_log.secs)
    stats = serve.run_engine_stream(eng, args)
    phase_s = time.perf_counter() - t0
    compiles = compile_log.secs[n0:]
    want = args.requests * args.new_tokens
    require(stats["requests"] == args.requests,
            f"{args.requests} requests answered ({stats['requests']})")
    # no request can exceed max_new_tokens, so the total pins each one
    require(stats["new_tokens"] == want,
            f"{want} new tokens ({stats['new_tokens']})")
    require(all(bool(jnp.isfinite(leaf).all())
                for leaf in jax.tree.leaves(eng.kv_pool)
                if jnp.issubdtype(leaf.dtype, jnp.floating)),
            "a finite KV pool")
    print(f"[{name}] {stats['requests']} requests answered in full "
          f"({stats['new_tokens']} new tokens); pool finite; "
          f"{len(compiles)} compiles {sum(compiles):.2f}s (longest "
          f"{max(compiles, default=0.0):.2f}s); warmup "
          f"{stats['warmup_s']:.2f}s; phase {phase_s:.2f}s; "
          f"{stats['decode_steps']} decode steps, ITL p50 "
          f"{stats.get('itl_p50_ms', float('nan')):.2f}ms; "
          f"{stats['tok_per_s']:.1f} tok/s (smoke, not a benchmark, "
          f"compiles inside the window); memory {memory(dev)}", flush=True)
    if args.prefix_cache:
        print(f"[{name}] prefix cache: {stats['cache_hits']} hits "
              f"({stats['cache_hit_pages']} pages), {stats['cow_copies']} "
              f"copy-on-writes", flush=True)
        require(stats["cache_hits"] > 0, "a prefix-cache hit")
        copy_on_write(eng, seed)
    if args.kv_bits < 16:
        hlo = eng.lower_decode().compile().as_text()
        has_kernel = "tpu_custom_call" in hlo
        print(f"[{name}] compiled decode program holds tpu_custom_call: "
              f"{has_kernel}", flush=True)
        require(has_kernel, "the Pallas kernel in the decode program")
        kernel_vs_reference(eng, args.kv_bits, seed)
    return stats


def build(cfg, w_bits: int, seed: int, dev):
    from repro.launch import serve
    args = serve.build_parser().parse_args(
        ["--arch", ARCH, "--w-bits", str(w_bits), "--seed", str(seed)])
    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.build_engine_params(cfg, args))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[build] W{w_bits} {cfg.n_layers} layers in "
          f"{time.perf_counter() - t0:.2f}s: {n_bytes / 1e9:.3f} GB of "
          f"served weights; memory {memory(dev)}", flush=True)
    return params


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    seed = ap.parse_args(argv).seed
    dev = check_device()
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(jax.devices())}", flush=True)
    compile_log = CompileLog()
    from repro.configs import base as cb
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[device] compile cache {enable_compile_cache()}", flush=True)

    cfg = cb.get(ARCH)
    params = build(cfg, 4, seed, dev)
    run_phase("w4_kv8", params, cfg, seed, dev, compile_log)
    gc.collect()                  # the first engine's pool goes first
    run_phase("w4_kv4", params, cfg, seed, dev, compile_log)
    del params
    gc.collect()

    cut = dataclasses.replace(cfg, n_layers=W16_LAYERS)
    print(f"[w16_kv16_cut] reduced: n_layers {cfg.n_layers} -> "
          f"{W16_LAYERS} (full-depth bf16 weights exceed one chip's HBM); "
          f"every width as published", flush=True)
    params = build(cut, 16, seed, dev)
    run_phase("w16_kv16_cut", params, cut, seed, dev, compile_log)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
