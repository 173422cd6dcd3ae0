"""Serving driver: batched generation with UNIQ-quantized weights.

Closed-batch smoke (legacy path):

    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_8b \
        --smoke --w-bits 4 --batch 4 --prompt-len 16 --new-tokens 32

Continuous-batching engine under a synthetic Poisson request stream
(reports tokens/s, time-to-first-token, slot occupancy, preemptions and
effective KV utilization):

    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_8b \
        --smoke --engine --w-bits 4 --requests 16 --rate 8 \
        --max-slots 8 --new-tokens 32 --page-size 64

The KV cache is paged by default (--cache-mode paged): sequences grow
page by page out of a shared pool (--total-pages or a --pool-bytes byte
budget; default sizes the pool to the slot-cache HBM) and are
preempted+resumed instead of evicted when it runs dry.  --kv-bits 8/4
stores the pages as k-quantile codes + per-row stats (half / ~a third
of the bytes, so a byte budget admits proportionally more sequences).
--cache-mode slot keeps the legacy fixed-region cache for A/B
comparison; --total-pages small enough forces preemption
(--min-preemptions asserts it happened, for CI smoke).

--prefix-cache turns on codes-domain prefix caching with chunked
prefill (DESIGN.md Sec. 7): admission attaches pool pages that already
hold a prompt's prefix instead of re-prefilling them, with
copy-on-write on divergence.  --prefill-chunk N prefills prompts N
pages at a time interleaved with decode (chunked prefill without the
cache).  --shared-prefix S prepends one fixed S-token system prompt to
every request so the stream actually shares prefixes;
--min-cache-hit-pages / --min-cow-copies assert the hit and COW paths
ran (CI smoke).  Every stream quantity — prompt tokens, lengths,
arrivals AND per-request sampling seeds — derives from the single
--seed, so a run is replayable end to end.

Engine mode builds random weights from --seed directly in serving form on
the device, one layer at a time (k-quantile codes at --w-bits 4/8, bf16
matmul weights at 16; models/lm.py::build_serving_params), so a published-
width model never exists as a dense f32 tree.  The closed-batch path
random-inits the float model, quantizes it, and reports greedy agreement
between the two.  Compute is bf16 (ModelOpts' default).  JAX's persistent
compile cache lives in $JAX_COMPILATION_CACHE_DIR, or else in the
checkout's .jax_cache (launch/compile_cache.py).
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cb
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.models.lm import ModelOpts
from repro.serve import serve as serve_lib
from repro.serve import telemetry as tele_lib
from repro.serve.engine import Engine, EngineConfig, Request, SamplingParams


# bf16 compute (ModelOpts' default).  Engine mode routes --a-bits through
# EngineConfig to the real per-token int8 codec (lm.mm_a), so the model's
# own layer-output fake-quant stays off (a_bits=32).
SERVE_OPTS = ModelOpts(remat=False, attn_chunked_min_len=1 << 30,
                       ssd_chunk=16)


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def engine_config(args) -> EngineConfig:
    return EngineConfig(max_slots=args.max_slots, max_len=args.max_len,
                        prefill_batch=args.prefill_batch,
                        cache_mode=args.cache_mode, page_size=args.page_size,
                        total_pages=args.total_pages, kv_bits=args.kv_bits,
                        a_bits=args.a_bits,
                        pool_bytes=args.pool_bytes,
                        prefix_cache=args.prefix_cache,
                        prefill_chunk=args.prefill_chunk,
                        checkify=args.checkify,
                        telemetry=not args.no_telemetry,
                        profile_annotations=args.profile_annotations
                        or bool(args.trace_out))


def run_engine_stream(eng: Engine, args) -> dict:
    """Drive the engine with a Poisson arrival stream (rate req/s).

    Requests are submitted when their arrival time passes on the wall
    clock, so TTFT includes genuine queueing delay under load.
    """
    cfg, ec = eng.cfg, eng.ec
    rng = np.random.default_rng(args.seed)
    n = args.requests
    sys_prompt = rng.integers(0, cfg.vocab, size=args.shared_prefix,
                              dtype=np.int64).astype(np.int32)
    lens = rng.integers(max(2, args.prompt_len // 2), args.prompt_len + 1,
                        size=n)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=n))
    # per-request sampling seeds derive from --seed too: the whole stream
    # (prompts, lengths, arrivals, sample paths) replays from one number
    seeds = rng.integers(0, 2 ** 31 - 1, size=n)
    reqs = [Request(uid=i,
                    prompt=np.concatenate([
                        sys_prompt,
                        rng.integers(0, cfg.vocab, size=int(lens[i]),
                                     dtype=np.int64).astype(np.int32)]),
                    sampling=SamplingParams(
                        temperature=args.temperature,
                        max_new_tokens=args.new_tokens,
                        seed=int(seeds[i])))
            for i in range(n)]

    if args.checkify:
        print("[engine] checkify sanitizer ON (index OOB + NaN checks per "
              "jitted step; debug mode — expect a host sync per step)")
    if args.cache_mode == "paged":
        sch = eng.scheduler
        print(f"[engine] paged KV pool: {sch.total_pages} pages x "
              f"{args.page_size} tokens at kv_bits={args.kv_bits} "
              f"({eng.page_bytes} B/page, "
              f"{sch.pool_bytes_total / 1024:.1f} KiB total)")

    # warm THIS engine's jitted steps (jit caches live on the instance):
    # compile the decode shape and EVERY prefill bucket this request set
    # will hit, outside the timed region
    from repro.serve.scheduler import bucket_len
    t_warm = time.perf_counter()
    seen = set()
    for r in reqs:
        b = min(bucket_len(r.prompt.size, ec.min_bucket), ec.max_len)
        if b not in seen:
            seen.add(b)
            eng.generate([Request(uid=-1 - len(seen), prompt=r.prompt.copy(),
                                  sampling=SamplingParams(max_new_tokens=2))])
    # warmup prompts must not pre-seed the prefix cache: hits below are
    # earned by the stream itself, not inherited from compile warming
    eng.flush_prefix_cache()
    eng.reset_stats()
    warmup_s = time.perf_counter() - t_warm
    print(f"[engine] warmup (compile + {len(seen)} warm requests) "
          f"{warmup_s:.2f}s")

    outs = []
    occupancy = []
    # --trace-out: the stream runs under the JAX profiler, the engine's
    # spans annotated (engine_config), so one Perfetto timeline holds the
    # engine's phases beside the device ops
    profile = jax.profiler.trace(args.trace_out, create_perfetto_trace=True) \
        if args.trace_out else contextlib.nullcontext()
    with profile:
        t0 = time.perf_counter()
        next_i = 0
        while next_i < n or eng.has_work:
            now = time.perf_counter() - t0
            while next_i < n and arrivals[next_i] <= now:
                reqs[next_i].arrival_time = t0 + arrivals[next_i]
                eng.submit(reqs[next_i])
                next_i += 1
            if not eng.has_work:
                time.sleep(min(1e-3, max(0.0, arrivals[next_i] - now)))
                continue
            outs.extend(eng.step())
            occupancy.append(eng.scheduler.n_running)
        wall = time.perf_counter() - t0
    if args.trace_out:
        print(f"[engine] profiler trace -> {args.trace_out} (open "
              f"plugins/profile/*/perfetto_trace.json.gz in "
              f"ui.perfetto.dev)")

    new_tokens = sum(len(o.token_ids) for o in outs)
    ttfts = [o.ttft_s for o in outs]
    lats = [o.latency_s for o in outs]
    stats = {
        "requests": len(outs),
        "new_tokens": new_tokens,
        "prompt_tokens": eng.n_prompt_tokens,
        "prefill_tokens": eng.n_prefill_tokens,  # > prompt on resumes
        "wall_s": wall,
        "tok_per_s": new_tokens / max(wall, 1e-9),
        "ttft_mean_s": float(np.mean(ttfts)) if ttfts else 0.0,
        "ttft_p50_s": _percentile(ttfts, 50),
        "ttft_p95_s": _percentile(ttfts, 95),
        "ttft_p99_s": _percentile(ttfts, 99),
        "latency_p50_s": _percentile(lats, 50),
        "decode_steps": eng.n_decode_steps,
        "prefill_calls": eng.n_prefill_calls,
        "mean_occupancy": float(np.mean(occupancy)) if occupancy else 0.0,
        "evicted": eng.scheduler.n_evicted,
        "preemptions": eng.n_preemptions,
        "kv_utilization": eng.kv_utilization,
        "warmup_s": warmup_s,
        **eng.stats(),
    }
    print(f"[engine] {stats['requests']} requests "
          f"({stats['prompt_tokens']} prompt + {new_tokens} new tokens) "
          f"in {wall:.2f}s -> {stats['tok_per_s']:.1f} new tok/s")
    print(f"[engine] TTFT mean {stats['ttft_mean_s'] * 1e3:.0f}ms "
          f"p50 {stats['ttft_p50_s'] * 1e3:.0f}ms "
          f"p95 {stats['ttft_p95_s'] * 1e3:.0f}ms; "
          f"latency p50 {stats['latency_p50_s'] * 1e3:.0f}ms")
    print(f"[engine] {stats['decode_steps']} decode steps, "
          f"{stats['prefill_calls']} prefill calls, mean occupancy "
          f"{stats['mean_occupancy']:.2f}/{args.max_slots} slots, "
          f"{stats['evicted']} evicted")
    if args.cache_mode == "paged":
        print(f"[engine] paged KV: {stats['preemptions']} preemptions, "
              f"effective utilization "
              f"{stats['kv_utilization'] * 100:.1f}% of held page rows")
        resumed = [o for o in outs if o.n_preempts > 0]
        if resumed:
            print(f"[engine] {len(resumed)} requests survived "
                  f"preempt/resume and completed")
        assert not any(o.finish_reason == "evicted" for o in outs), \
            "paged mode must never evict terminally"
    if args.prefix_cache:
        hit_rate = stats["cache_hits"] / max(stats["cache_lookups"], 1)
        print(f"[engine] prefix cache: {stats['cache_hits']}/"
              f"{stats['cache_lookups']} admissions hit "
              f"({hit_rate * 100:.0f}%), {stats['cache_hit_pages']} pages "
              f"attached ({stats['cache_hit_tokens']} tokens), "
              f"{stats['cow_copies']} copy-on-writes, "
              f"{stats['cache_evictions']} LRU evictions, "
              f"{stats['cached_pages']} pages cached at end")
    if args.min_cache_hit_pages and \
            stats["cache_hit_pages"] < args.min_cache_hit_pages:
        raise SystemExit(
            f"expected >= {args.min_cache_hit_pages} cache-hit pages, saw "
            f"{stats['cache_hit_pages']} — prefix-cache hit path not "
            f"exercised")
    if args.min_cow_copies and stats["cow_copies"] < args.min_cow_copies:
        raise SystemExit(
            f"expected >= {args.min_cow_copies} copy-on-writes, saw "
            f"{stats['cow_copies']} — COW divergence path not exercised")
    if args.min_preemptions and stats["preemptions"] < args.min_preemptions:
        raise SystemExit(
            f"expected >= {args.min_preemptions} preemptions, saw "
            f"{stats['preemptions']} — scheduler preempt path not exercised")
    if stats["requests"] != eng.scheduler.n_submitted:
        raise SystemExit(
            f"lost requests: {eng.scheduler.n_submitted} submitted, "
            f"{stats['requests']} completed")

    # -- telemetry exports ----------------------------------------------------
    if eng.telemetry.enabled:
        reg = eng.telemetry.registry
        itl = tele_lib.percentile_summary(reg["itl_s"], scale=1e3)
        qw = tele_lib.percentile_summary(reg["queue_wait_s"], scale=1e3)
        print(f"[engine] ITL p50 {itl['p50']:.1f}ms p95 {itl['p95']:.1f}ms "
              f"p99 {itl['p99']:.1f}ms; queue wait p50 {qw['p50']:.1f}ms "
              f"p95 {qw['p95']:.1f}ms")
        stats.update({f"itl_{k}_ms": v for k, v in itl.items()})
        stats.update({f"queue_wait_{k}_ms": v for k, v in qw.items()})
    if args.metrics_out:
        # the driver knows what the engine doesn't: quantizer + workload
        meta = {"w_bits": args.w_bits, "a_bits": args.a_bits,
                "dist": args.w_dist, "smoke": args.smoke,
                "rate": args.rate, "requests": args.requests,
                "shared_prefix": args.shared_prefix}
        eng.telemetry.write_metrics(args.metrics_out,
                                    eng.config_meta() | meta)
        print(f"[engine] metrics snapshot -> {args.metrics_out} "
              f"(+ .prom exposition)")
    return stats


def run_closed_batch(params, cfg, opts, args) -> None:
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    sc = serve_lib.ServeConfig(w_bits=args.w_bits, a_bits=args.a_bits,
                               w_dist=args.w_dist)

    out_fp = serve_lib.generate(params, cfg, opts, sc, prompts,
                                args.new_tokens)
    t0 = time.time()
    params_q = serve_lib.prepare_params(params, sc)
    sopts = serve_lib.make_serve_opts(opts, sc)
    out_q = serve_lib.generate(params_q, cfg, sopts, sc, prompts,
                               args.new_tokens) \
        if args.w_bits < 16 else out_fp
    dt = time.time() - t0
    match = float(jnp.mean((out_fp == out_q).astype(jnp.float32)))
    n_tok = args.batch * args.new_tokens
    print(f"[serve] {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s host-loop)")
    print(f"[serve] W{args.w_bits} greedy agreement with bf16: "
          f"{match * 100:.1f}%")
    print("sample (quantized):", out_q[0][:16].tolist())


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--w-bits", type=int, default=4)
    p.add_argument("--w-dist", choices=("gaussian", "empirical"),
                   default="gaussian",
                   help="weight dequant levels: analytic Gaussian or the "
                        "empirical per-tensor codebook (LUT) — match the "
                        "checkpoint's training cfg.dist")
    p.add_argument("--a-bits", type=int, default=32,
                   help="activation bit-width: closed-batch mode applies "
                        "layer-output fake-quant; --engine mode serves a "
                        "real per-token int8 codec on every quantized "
                        "matmul (prefill + decode) and reports it in the "
                        "metrics meta for BOPs attribution")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--new-tokens", type=int, default=32)
    p.add_argument("--seed", type=int, default=0)
    # engine mode
    p.add_argument("--engine", action="store_true",
                   help="continuous-batching engine + Poisson stream")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--rate", type=float, default=8.0,
                   help="Poisson arrival rate (requests/s)")
    p.add_argument("--max-slots", type=int, default=8)
    p.add_argument("--max-len", type=int, default=256,
                   help="per-sequence KV capacity (prompt + generation)")
    p.add_argument("--prefill-batch", type=int, default=4)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--cache-mode", choices=("paged", "slot"),
                   default="paged")
    p.add_argument("--page-size", type=int, default=64,
                   help="KV page size in tokens (paged mode)")
    p.add_argument("--total-pages", type=int, default=None,
                   help="KV pool size; default = slot-cache-equivalent "
                        "HBM; smaller values force preemption/resume")
    p.add_argument("--kv-bits", type=int, default=16, choices=(16, 8, 4),
                   help="KV page bit-width: 8/4 store k-quantile codes + "
                        "per-row stats (paged mode only)")
    p.add_argument("--pool-bytes", type=int, default=None,
                   help="KV pool byte budget (alternative to "
                        "--total-pages): pages = pool_bytes // page bytes "
                        "at the chosen --kv-bits")
    p.add_argument("--prefix-cache", action="store_true",
                   help="codes-domain prefix caching over pool pages "
                        "(implies chunked prefill; paged mode only)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill chunk size in pages: prompts prefill "
                        "chunk by chunk interleaved with decode (paged "
                        "mode; default 1 when --prefix-cache is on)")
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="prepend one fixed system prompt of this many "
                        "tokens to every request (prefix-cache workload)")
    p.add_argument("--min-preemptions", type=int, default=0,
                   help="fail unless at least this many preemptions "
                        "happened (CI smoke of the preempt/resume path)")
    p.add_argument("--min-cache-hit-pages", type=int, default=0,
                   help="fail unless at least this many prefix-cache "
                        "pages were attached (CI smoke of the hit path)")
    p.add_argument("--min-cow-copies", type=int, default=0,
                   help="fail unless at least this many copy-on-writes "
                        "happened (CI smoke of the divergence path)")
    # observability (serve/telemetry.py; DESIGN.md Sec. 11)
    p.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the metrics snapshot JSON here (plus the "
                        "Prometheus text exposition at PATH.prom)")
    p.add_argument("--trace-out", default=None, metavar="DIR",
                   help="run the request stream under the JAX profiler "
                        "and write its trace directory here, engine spans "
                        "annotated (implies --profile-annotations; open "
                        "the perfetto_trace.json.gz in ui.perfetto.dev)")
    p.add_argument("--no-telemetry", action="store_true",
                   help="disable metrics + tracing (A/B the overhead; "
                        "token streams are bit-identical either way)")
    p.add_argument("--profile-annotations", action="store_true",
                   help="open a jax.profiler TraceAnnotation engine.<span> "
                        "with every engine span, so the engine's phases "
                        "show up in device profiles captured by "
                        "jax.profiler")
    # opt-in debug sanitizers (both OFF by default; DESIGN.md Sec. 10)
    p.add_argument("--checkify", action="store_true",
                   help="wrap the engine's jitted steps with "
                        "jax.experimental.checkify index-OOB + NaN "
                        "checks (debug runs; slow — host sync per step)")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax_debug_nans globally (first NaN "
                        "raises with a traceback; debug runs only)")
    return p


def build_engine_params(cfg, args):
    """Random weights from --seed, built in serving form on the device."""
    return model.build_for_serving(jax.random.PRNGKey(args.seed), cfg,
                                   args.w_bits, dist=args.w_dist)


def main(argv=None):
    p = build_parser()
    args = p.parse_args(argv)

    if (args.metrics_out or args.trace_out) and not args.engine:
        p.error("--metrics-out/--trace-out require --engine")
    if args.metrics_out and args.no_telemetry:
        p.error("--metrics-out needs telemetry enabled (drop "
                "--no-telemetry)")

    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)

    enable_compile_cache()
    cfg = cb.get_smoke(args.arch) if args.smoke else cb.get(args.arch)
    if args.engine:
        params = build_engine_params(cfg, args)
        run_engine_stream(Engine(params, cfg, SERVE_OPTS,
                                 engine_config(args)), args)
    else:
        params = model.init(jax.random.PRNGKey(args.seed), cfg)
        run_closed_batch(params, cfg, SERVE_OPTS, args)


if __name__ == "__main__":
    main()
