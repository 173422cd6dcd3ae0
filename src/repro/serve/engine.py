"""Continuous-batching serving engine over UNIQ-quantized weights.

The legacy ``serve.generate`` path prefills token-by-token with one fixed
batch: every request in the batch must arrive together, finish together,
and pay a host-loop step per *prompt* token.  This engine serves an open
request stream instead (DESIGN.md Sec. 6):

  * **paged KV cache** (default) — one device-resident page pool
    (leaves (L, total_pages, page_size, ...)); a sequence's KV grows page
    by page through a per-slot block table (a traced (max_slots, n_pages)
    int32 array, so growth never recompiles).  Short requests stop
    paying for ``max_len``-sized reservations, and on pool exhaustion
    the scheduler *preempts* the lowest-priority sequence (frees its
    pages, requeues it with its generated tokens) and *resumes* it later
    by re-prefilling prompt+generated — no request is ever lost
    mid-decode.  A legacy **slot** mode (fixed max_len region per slot,
    terminal eviction) is kept as the A/B baseline.
  * **bit-parametric pages** — ``kv_bits`` in {16, 8, 4}: quantized pools
    hold k-quantile codes + per-(row, head) statistics instead of dense
    bf16 rows (models/kv_cache.py); prefill codes K/V before attending
    and decode appends codes, so preemption/resume is bit-exact in the
    codes domain.  The scheduler admits in *bytes* (``pool_bytes``), so
    at equal HBM the kv8/kv4 pool holds ~2x/~3.6x the pages — quantized
    KV trades directly into concurrency.
  * **batched prefill** — an admitted group runs ONE forward over the
    whole padded prompt block (``model.prefill`` with per-sequence
    ``last_idx``), then scatters its KV into pool pages
    (``model.cache_insert_paged``) or slots (``model.cache_insert``).
    Prompt cost drops from S0 host-loop decode steps to a single jit call.
  * **continuous decode** — one jitted fixed-shape step advances *all*
    active slots each iteration; sequences join and leave mid-stream
    without disturbing the others.
  * **per-request sampling** — temperature / top-k / stop conditions are
    per-slot *arrays* traced into the step, and sample keys are folded by
    (seed, position) — never by slot or batch — so a resumed sequence's
    sample stream continues exactly where preemption cut it.
  * **chunked prefill + prefix caching** (``prefill_chunk`` /
    ``prefix_cache``, DESIGN.md Sec. 7) — instead of one whole padded
    prefill at admission, a prompt is prefilled page-chunk by page-chunk
    (one fixed (1, chunk) jit shape), interleaved with decode steps so
    running decodes never stall behind a long prompt.  With the prefix
    cache on, admission attaches pages already holding the prompt's
    prefix (radix lookup over token-id page chunks; exact in the codes
    domain) and prefill starts after the hit; shared pages are
    copy-on-written before any write (``clone_pages``), and completed
    prompts' pages are registered for future hits.

Fixed jit shapes: the decode step always sees (max_slots, 1) tokens (plus
the block-table array in paged mode); the prefill sees (prefill_batch,
bucket) token blocks, bucket a power of two — the compile count is
bounded by the bucket count, not the traffic.

The weights may be k-quantile coded (``model.build_for_serving`` or
``model.quantize_for_serving``): both prefill and decode then dequantize
on the fly (``lm.materialize`` + ``jnp.dot``), which is the deployment
regime the paper's BOPs argument targets (EXPERIMENTS.md Sec. Perf).
"""

from __future__ import annotations

import dataclasses
import logging
import re
import time
from typing import Dict, List, Optional, Sequence as Seq, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import kv_cache
from repro.models import model
from repro.models.lm import ModelOpts
from repro.serve import telemetry as tele_lib
from repro.serve.scheduler import (Request, SamplingParams, ScheduledSeq,
                                   Scheduler, Sequence, pages_for)

__all__ = ["EngineConfig", "Engine", "Request", "SamplingParams",
           "RequestOutput", "OP_SCOPES", "AMBIGUOUS", "hlo_op_scopes"]

_log = logging.getLogger(__name__)

# smallest bucketed decode batch: engines at or below this never bucket
# (one compiled decode shape, exactly the pre-bucketing behavior), so the
# small-slot engines tests and model checking build stay single-graph
_DECODE_BUCKET_MIN = 8


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8          # concurrent sequences (decode batch)
    max_len: int = 256          # per-sequence KV capacity (prompt + gen)
    prefill_batch: int = 4      # prompts prefilled per admission round
    min_bucket: int = 16        # smallest padded prompt length
    cache_mode: str = "paged"   # "paged" | "slot" (legacy A/B baseline)
    page_size: int = 64         # KV page size in tokens (paged mode)
    total_pages: Optional[int] = None
    # total_pages None => max_slots * ceil(max_len/page_size) + 1: the same
    # KV HBM as the slot cache plus the reserved sink page, i.e. enough
    # that preemption only triggers when the pool is deliberately shrunk.
    kv_bits: int = 16           # 8/4 => k-quantile-coded KV pages (paged
                                #   mode only; models/kv_cache.py)
    a_bits: int = 32            # 8 => serve activations through the real
                                #   per-token int8 codec on quantized
                                #   matmuls (lm.mm_a, the qmatmul_a8
                                #   regime) in BOTH prefill and decode;
                                #   32 = full-precision activations.
                                #   Surfaces in the metrics-snapshot meta.
    pool_bytes: Optional[int] = None
    # byte budget for the page pool (alternative to total_pages): the pool
    # holds pool_bytes // page_kv_bytes(cfg, page_size, kv_bits) pages —
    # the dense kv16 page cost is charged at the dtype the pool is
    # actually allocated in, so the budget bounds real memory — and the
    # same budget admits ~2x the tokens at kv_bits=8, ~3.6x at 4: the
    # equal-HBM concurrency trade the benchmark sweeps.
    prefix_cache: bool = False  # radix prefix cache over pool pages (paged
                                #   mode; implies chunked prefill so hits
                                #   can skip the cached prefix)
    prefill_chunk: Optional[int] = None
    # pages per prefill chunk (paged mode): prompts prefill chunk-by-chunk
    # interleaved with decode steps instead of one whole padded prefill.
    # None with prefix_cache=True defaults to 1 page per chunk.
    coalesce_prefill: bool = True
    # batch every mid-prefill slot's next chunk into ONE prefill_chunk
    # call per engine step (padded to a power-of-two batch) instead of a
    # B=1 call per slot.  Bit-exact either way (pinned in tests; the
    # ``prefill_chunk_calls_saved`` counter tallies the coalesced calls);
    # False keeps the sequential path for A/B.
    bucket_decode: bool = True
    # paged mode: run the decode step at the power-of-two bucket of the
    # *active* slot count (floor 8, cap max_slots) instead of always at
    # max_slots — active rows are gathered into the bucket, pad rows
    # write the sink page.  A drained 43-slot pool decoding 5 stragglers
    # otherwise pays the full 43-row step (the fixed-shape padding tax
    # the kv4 equal-HBM sweep exposes).  Bit-exact either way: sampling
    # folds on (seed, position), never slot or batch (pinned in tests).
    # Engines with max_slots <= 8 never bucket (one compiled shape, as
    # before); larger engines compile O(log max_slots/8) decode graphs.
    checkify: bool = False
    # opt-in debug sanitizer (OFF by default — it forces a host sync and
    # error bookkeeping per step): wraps every jitted step with
    # jax.experimental.checkify index-OOB + NaN checks, so a bad block
    # table / position or a NaN in logits raises at the offending step
    # instead of corrupting the pool silently.  --checkify on
    # launch/serve.py and benchmarks/engine_bench.py.
    telemetry: bool = True
    # structured observability (serve/telemetry.py, DESIGN.md Sec. 11):
    # latency/queue histograms, occupancy gauges and the engine's phase
    # spans in a bounded ring buffer, exportable as a metrics snapshot.
    # Host-side and O(1) per step; token streams are bit-identical on/off
    # (pinned in tests).  False = the null object: same code path,
    # records nothing.
    trace_capacity: int = 65536
    # span/instant ring-buffer capacity; oldest whole spans drop first
    profile_annotations: bool = False
    # every engine span also opens a jax.profiler TraceAnnotation named
    # engine.<span> (StepTraceAnnotation for engine.step), so the phases
    # sit on the device profile's host clock (jax.profiler.trace,
    # Perfetto).  OFF by default: about a microsecond per span even when
    # no profiler is attached.


@dataclasses.dataclass
class RequestOutput:
    uid: int
    prompt: np.ndarray
    token_ids: List[int]
    finish_reason: str          # "stop" | "length" | "evicted" (slot mode)
    ttft_s: float               # arrival -> first token (wall clock)
    latency_s: float            # arrival -> completion (wall clock)
    n_preempts: int = 0         # preempt/resume round-trips survived


def _sample_batch(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                  top_ks: jax.Array) -> jax.Array:
    """Per-row sampling: greedy at temperature 0, else categorical with an
    optional top-k filter.  All controls are traced arrays (B,)."""
    V = logits.shape[-1]

    def one(lg, key, t, k):
        greedy = jnp.argmax(lg).astype(jnp.int32)
        lt = lg.astype(jnp.float32) / jnp.maximum(t, 1e-6)
        kth = jnp.sort(lt)[::-1][jnp.clip(k - 1, 0, V - 1)]
        lt = jnp.where((k > 0) & (lt < kth), -jnp.inf, lt)
        samp = jax.random.categorical(key, lt).astype(jnp.int32)
        return jnp.where(t <= 0.0, greedy, samp)

    return jax.vmap(one)(logits, keys, temps, top_ks)


def _fold_keys(seeds: jax.Array, positions: jax.Array) -> jax.Array:
    """Deterministic per-(seed, position) keys: a request's sample stream
    does not depend on which slot or batch it lands in — and therefore
    survives preemption/resume bit-exactly."""
    base = jax.random.PRNGKey(0)
    return jax.vmap(lambda s, p: jax.random.fold_in(
        jax.random.fold_in(base, s), p))(seeds, positions)


def _sample(logits: jax.Array, seeds: jax.Array, positions: jax.Array,
            temps: jax.Array, top_ks: jax.Array) -> jax.Array:
    """``_sample_batch`` with keys folded at ``positions``, under the
    ``sample`` scope."""
    with jax.named_scope("sample"):
        return _sample_batch(logits, _fold_keys(seeds, positions), temps,
                             top_ks)


# The named scopes the step programs open (models/lm.py and ``_sample``):
# every op of the model's own computation carries one of them in its
# op_name; ``dequant`` is always nested under ``matmul``.
OP_SCOPES = ("layer", "matmul", "dequant", "attention", "kv_write", "embed",
             "head", "sample")
AMBIGUOUS = "?"     # one name, different scopes in two compiled buckets

_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: innermost ``OP_SCOPES`` entry in
    its op_name, or ""}) of a compiled module's HLO text, every
    computation's instructions included."""
    module, scopes = "", {}
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            if not module:
                hm = _HLO_MODULE.match(line)
                module = hm.group(1) if hm else module
            continue
        op = _HLO_OP_NAME.search(line)
        parts = op.group(1).split("/") if op else ()
        scopes[m.group(1)] = next(
            (p for p in reversed(parts) if p in OP_SCOPES), "")
    return module, scopes


class Engine:
    """Continuous-batching engine.  ``submit`` requests, call ``step`` in a
    loop (or ``generate`` for a closed set); finished ``RequestOutput``s
    are returned as they complete."""

    def __init__(self, params, cfg: ArchConfig, opts: ModelOpts,
                 ec: EngineConfig = EngineConfig()):
        if not model.supports_slot_cache(cfg):
            raise ValueError(
                f"engine serves decoder-only KV families; got {cfg.family}")
        if ec.cache_mode not in ("paged", "slot"):
            raise ValueError(f"unknown cache_mode: {ec.cache_mode!r}")
        kv_cache.check_kv_bits(ec.kv_bits, cfg.head_dim)
        if ec.kv_bits < 16 and ec.cache_mode != "paged":
            raise ValueError("kv_bits < 16 requires the paged cache (the "
                             "slot mode is the dense legacy baseline)")
        if ec.pool_bytes is not None and ec.cache_mode != "paged":
            raise ValueError("pool_bytes sizes the paged pool; the slot "
                             "cache is fixed at max_slots * max_len")
        if (ec.prefix_cache or ec.prefill_chunk is not None) \
                and ec.cache_mode != "paged":
            raise ValueError("prefix_cache / prefill_chunk require the "
                             "paged cache")
        if ec.prefill_chunk is not None and ec.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 page")
        if ec.a_bits != 32 and not 2 <= ec.a_bits <= 8:
            raise ValueError("a_bits must be 32 (off) or 2..8 (the int8 "
                             f"activation codec); got {ec.a_bits}")
        self.cfg, self.ec = cfg, ec
        self.paged = ec.cache_mode == "paged"
        self.chunked = self.paged and (ec.prefix_cache
                                       or ec.prefill_chunk is not None)
        self.chunk_tokens = (ec.prefill_chunk or 1) * ec.page_size \
            if self.paged else 0
        self.opts = dataclasses.replace(opts, remat=False,
                                        kv_bits=ec.kv_bits,
                                        serve_a_bits=ec.a_bits)
        self.params = params
        cache_dtype = jnp.float32 if opts.compute_dtype == jnp.float32 \
            else jnp.bfloat16
        if self.paged:
            self.page_bytes = kv_cache.page_kv_bytes(
                cfg, ec.page_size, ec.kv_bits,
                dense_itemsize=jnp.dtype(cache_dtype).itemsize)
            self.scheduler = Scheduler(ec.max_slots, ec.prefill_batch,
                                       ec.min_bucket, ec.max_len,
                                       page_size=ec.page_size,
                                       total_pages=ec.total_pages,
                                       page_bytes=self.page_bytes,
                                       pool_bytes=ec.pool_bytes,
                                       prefix_cache=ec.prefix_cache)
            self._cache = model.init_paged_cache(
                cfg, self.scheduler.total_pages, ec.page_size, cache_dtype,
                kv_bits=ec.kv_bits)
        else:
            self.scheduler = Scheduler(ec.max_slots, ec.prefill_batch,
                                       ec.min_bucket, ec.max_len)
            self._cache = model.init_slot_cache(cfg, ec.max_slots,
                                                ec.max_len, cache_dtype)
        M = ec.max_slots
        self._positions = np.zeros((M,), np.int32)   # next KV write index
        self._cur_tok = np.zeros((M,), np.int32)     # last sampled token
        self._temps = np.zeros((M,), np.float32)
        self._topks = np.zeros((M,), np.int32)
        self._seeds = np.zeros((M,), np.int32)
        self._slots: dict[int, Sequence] = {}        # active slot -> seq
        self._prefilling: dict[int, Sequence] = {}   # mid-chunked-prefill
        self.n_decode_steps = 0
        self.n_bucketed_steps = 0   # decode steps run below max_slots
        self.n_prefill_calls = 0
        self.n_prefill_tokens = 0   # prefill *work* (resumes re-pay)
        self.n_prompt_tokens = 0    # unique prompt tokens (first admit only)
        # KV utilization accumulators (paged): valid rows vs held page rows
        self._util_tokens = 0
        self._util_page_tokens = 0
        self._last_decode_end: Optional[float] = None  # ITL anchor
        self.n_steps = 0
        # (program, batch bucket) -> (jitted fn, argument shapes) of each
        # step program dispatched so far, for ``op_scopes``
        self._dispatched: Dict[tuple, tuple] = {}

        # -- telemetry (DESIGN.md Sec. 11): histograms observed on the
        # hot path are O(1) bisects; everything state-shaped is a gauge
        # refreshed by the collector at snapshot time only
        annotate = None
        if ec.profile_annotations:
            def annotate(name):
                if name == "step":
                    return jax.profiler.StepTraceAnnotation(
                        "engine.step", step_num=self.n_steps)
                return jax.profiler.TraceAnnotation("engine." + name)
        self.telemetry = tele_lib.Telemetry(enabled=ec.telemetry,
                                            trace_capacity=ec.trace_capacity,
                                            annotate=annotate)
        reg = self.telemetry.registry
        self._m_ttft = reg.histogram("ttft_s", help="arrival -> first token")
        self._m_itl = reg.histogram(
            "itl_s", help="gap between consecutive decode steps "
            "(= inter-token latency for every active sequence)")
        self._m_queue_wait = reg.histogram(
            "queue_wait_s", help="arrival -> admission")
        self._m_e2e = reg.histogram("e2e_latency_s",
                                    help="arrival -> completion")
        self._m_decode_step = reg.histogram(
            "decode_step_s", help="jitted decode step incl. host sync")
        self._m_prefill_call = reg.histogram(
            "prefill_call_s", help="batched whole-prompt prefill call")
        self._m_chunk_call = reg.histogram(
            "prefill_chunk_s", help="single chunked-prefill call")
        self._m_batch = reg.histogram(
            "decode_batch", tele_lib.linear_buckets(0, 1, ec.max_slots),
            help="active decode slots per step")
        self._m_tok_decode = reg.counter(
            "tokens_decoded", help="tokens sampled by decode steps")
        self._m_tok_prefill_step = reg.counter(
            "prefill_step_tokens",
            help="prompt tokens run while decode slots were active "
            "(chunked-prefill interleaving)")
        self._m_chunk_saved = reg.counter(
            "prefill_chunk_calls_saved",
            help="B=1 prefill_chunk calls avoided by coalescing the "
            "step's mid-prefill slots into one batched call")
        reg.counter("requests_submitted")
        for reason in ("stop", "length", "evicted"):
            reg.counter(f"requests_finished_{reason}")
        self.telemetry.registry.add_collector(self._collect_gauges)

        cfg_, opts_ = self.cfg, self.opts

        def decode_slot(params, cache, tokens, positions, temps, topks,
                        seeds):
            logits, cache = model.decode(params, cfg_, opts_, cache,
                                         tokens[:, None], positions)
            return _sample(logits, seeds, positions, temps, topks), cache

        def decode_paged(params, cache, tokens, positions, block_tables,
                         temps, topks, seeds):
            logits, cache = model.decode(params, cfg_, opts_, cache,
                                         tokens[:, None], positions,
                                         block_tables=block_tables)
            return _sample(logits, seeds, positions, temps, topks), cache

        def prefill_fn(params, tokens, last_idx, temps, topks, seeds):
            logits, kv = model.prefill(params, cfg_, opts_,
                                       {"tokens": tokens}, last_idx=last_idx)
            return _sample(logits, seeds, last_idx, temps, topks), kv

        def chunk_fn(params, cache, tokens, positions, write_pages,
                     write_rows, block_tables, last_idx, last_pos, temps,
                     topks, seeds):
            logits, cache = model.prefill_chunk(
                params, cfg_, opts_, cache, tokens, positions, write_pages,
                write_rows, block_tables, last_idx)
            # fold at the prompt's absolute last position: the sampled
            # first token matches whole-prefill (and preempt/resume) bit
            # for bit, whichever chunking produced it
            return _sample(logits, seeds, last_pos, temps, topks), cache

        def copy_fn(cache, src, dst):
            return kv_cache.clone_pages(cache, src, dst)

        def _jit(fn, donate=()):
            """jit a step fn; with ec.checkify, route it through
            jax.experimental.checkify (index OOB + NaN) first.  The
            checkified fn keeps the positional signature, so
            donate_argnums indices carry over unchanged; the python shim
            throws the accumulated error after each call (a host sync —
            debug mode only)."""
            if not ec.checkify:
                return jax.jit(fn, donate_argnums=donate)
            from jax.experimental import checkify as _ck
            errs = _ck.index_checks | _ck.nan_checks
            checked = jax.jit(_ck.checkify(fn, errors=errs),
                              donate_argnums=donate)

            def shim(*args):
                err, out = checked(*args)
                _ck.check_error(err)
                return out
            shim.lower = checked.lower
            return shim

        self._decode_step = _jit(decode_paged if self.paged else decode_slot,
                                 donate=(1,))
        self._prefill_step = _jit(prefill_fn)
        self._cache_insert = _jit(
            model.cache_insert_paged if self.paged else model.cache_insert,
            donate=(0,))
        self._chunk_step = _jit(chunk_fn, donate=(1,))
        self._copy_pages = _jit(copy_fn, donate=(0,))

    def _collect_gauges(self) -> None:
        """Snapshot-time collector: mirror engine/scheduler state into the
        registry.  Never runs on the hot path."""
        s = self.scheduler
        reg = self.telemetry.registry
        reg.counter("decode_steps").value = self.n_decode_steps
        reg.counter("prefill_calls").value = self.n_prefill_calls
        reg.counter("prefill_tokens").value = self.n_prefill_tokens
        reg.counter("prompt_tokens").value = self.n_prompt_tokens
        reg.counter("kv_rows_attended").value = self._util_tokens
        reg.counter("kv_page_rows_held").value = self._util_page_tokens
        reg.counter("requests_completed").value = s.n_completed
        reg.counter("preemptions").value = s.n_preemptions
        reg.counter("cache_lookups").value = s.n_cache_lookups
        reg.counter("cache_hits").value = s.n_cache_hits
        reg.counter("cache_hit_tokens").value = s.n_cache_hit_tokens
        reg.counter("cache_hit_pages").value = s.n_cache_hit_pages
        reg.counter("cow_copies").value = s.n_cow_copies
        reg.counter("cache_evictions").value = s.n_cache_evictions
        reg.counter("trace_spans_dropped").value = \
            self.telemetry.tracer.n_dropped
        reg.gauge("slots_running").set(s.n_running)
        reg.gauge("slots_prefilling").set(len(self._prefilling))
        reg.gauge("queue_depth").set(s.n_waiting)
        reg.gauge("kv_utilization").set(self.kv_utilization)
        if self.paged:
            reg.gauge("pages_in_use").set(s.pages_in_use)
            reg.gauge("pages_free").set(s.n_free_pages)
            reg.gauge("bytes_in_use").set(s.bytes_in_use)
            reg.gauge("pool_bytes_total").set(s.pool_bytes_total)
            reg.gauge("cached_pages").set(s.cached_pages)
            if s.prefix_cache is not None:
                reg.gauge("prefix_cache_nodes").set(s.prefix_cache.n_nodes)

    # -- request side ------------------------------------------------------

    def submit(self, request: Request) -> None:
        # None (not a 0.0 sentinel) means "unset": a driver that really
        # measured an arrival at t=0.0 keeps it, and TTFT is always
        # anchored at true arrival
        if request.arrival_time is None:
            request.arrival_time = time.perf_counter()
        self.scheduler.submit(request)
        self.telemetry.inc(
            self.telemetry.registry.counter("requests_submitted"))

    def reset_stats(self) -> None:
        """Zero perf counters (e.g. after a compile-warmup request); the
        jit caches and slot state are untouched."""
        self.n_decode_steps = 0
        self.n_bucketed_steps = 0
        self.n_prefill_calls = 0
        self.n_prefill_tokens = 0
        self.n_prompt_tokens = 0
        self._util_tokens = 0
        self._util_page_tokens = 0
        self._last_decode_end = None
        self.telemetry.reset()
        self.scheduler.n_submitted = 0
        self.scheduler.n_completed = 0
        self.scheduler.n_evicted = 0
        self.scheduler.n_preemptions = 0
        self.scheduler.n_cache_lookups = 0
        self.scheduler.n_cache_hits = 0
        self.scheduler.n_cache_hit_tokens = 0
        self.scheduler.n_cache_hit_pages = 0
        self.scheduler.n_cow_copies = 0
        self.scheduler.n_cache_evictions = 0

    def flush_prefix_cache(self) -> int:
        """Drop every prefix-cache registration (pages return to the free
        list unless still shared with a running sequence).  Benchmarks
        call this after warmup so hits are earned, not inherited."""
        return self.scheduler.flush_prefix_cache()

    def stats(self) -> dict:
        """Legacy flat counter dict (perf reports, CI assertions) — now a
        view over the metrics registry; ``metrics_snapshot()`` is the
        full structured export."""
        self.telemetry.registry.collect()
        reg = self.telemetry.registry
        out = {k: reg.counter(k).value for k in (
            "preemptions", "cache_lookups", "cache_hits",
            "cache_hit_tokens", "cache_hit_pages", "cow_copies",
            "cache_evictions", "prefill_chunk_calls_saved")}
        out["cached_pages"] = self.scheduler.cached_pages
        return out

    def config_meta(self) -> dict:
        """Engine-side facts for the metrics snapshot ``meta`` block (the
        launcher adds what only it knows — w_bits, dist)."""
        ec, cfg = self.ec, self.cfg
        meta = {
            "arch": cfg.name, "family": cfg.family,
            "cache_mode": ec.cache_mode, "kv_bits": ec.kv_bits,
            "a_bits": ec.a_bits,
            "page_size": ec.page_size, "max_slots": ec.max_slots,
            "max_len": ec.max_len, "prefill_batch": ec.prefill_batch,
            "prefix_cache": ec.prefix_cache,
            "prefill_chunk": ec.prefill_chunk,
            "bucket_decode": ec.bucket_decode,
            "telemetry": ec.telemetry,
        }
        if self.paged:
            meta["page_bytes"] = self.page_bytes
            meta["total_pages"] = self.scheduler.total_pages
            meta["token_kv_bytes"] = self.page_bytes // ec.page_size
        return meta

    def metrics_snapshot(self, meta: Optional[dict] = None) -> dict:
        """Stable JSON-serializable snapshot of every metric, gauges
        refreshed; ``meta`` is merged over ``config_meta()``."""
        m = self.config_meta()
        m.update(meta or {})
        return self.telemetry.snapshot(m)

    def op_scopes(self) -> Dict[str, Dict[str, str]]:
        """{program: {HLO instruction name: scope}} of every step program
        and batch bucket dispatched so far, read from the compiled module
        text of each (compiled already, so this costs a lowering and a
        cache lookup per program).  A scope is the innermost ``OP_SCOPES``
        entry in the instruction's op_name, "" when it has none (the
        layer scan's slicing of the weights, compiler-inserted copies);
        a name two buckets of one program give different scopes is
        ``AMBIGUOUS``.  Programs are named as the device profile's
        ``XLA Modules`` line names them (``jit_decode_paged``)."""
        t0 = time.perf_counter()
        out: Dict[str, Dict[str, str]] = {}
        for fn, args in self._dispatched.values():
            module, scopes = hlo_op_scopes(
                fn.lower(*args).compile().as_text())
            merged = out.setdefault(module, {})
            for name, scope in scopes.items():
                merged[name] = scope if merged.get(name, scope) == scope \
                    else AMBIGUOUS
        _log.info("op_scopes: %d programs (%d compiled shapes) in %.3fs",
                  len(out), len(self._dispatched), time.perf_counter() - t0)
        return out

    def _run(self, key: tuple, fn, lead: tuple, host: list):
        """Upload the numpy inputs ``host`` and call ``fn(*lead, *host)``,
        under the ``upload`` and ``dispatch`` spans.  The first call of
        each ``key`` (program, batch bucket) keeps the argument shapes
        for ``op_scopes``."""
        with self.telemetry.span("upload"):
            args = lead + tuple(jnp.asarray(a) for a in host)
        if key not in self._dispatched:
            self._dispatched[key] = (fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        with self.telemetry.span("dispatch"):
            return fn(*args)

    @property
    def kv_pool(self):
        """The device KV pool pytree (read it; the engine owns it)."""
        return self._cache

    def lower_decode(self):
        """The engine's own jitted decode step lowered at this engine's
        shapes (a ``jax.stages.Lowered``): ``.compile().as_text()`` is the
        program the device runs, e.g. to check which kernels it holds."""
        args = [self.params, self._cache, self._cur_tok, self._positions]
        if self.paged:
            args.append(self.scheduler.block_tables)
        args += [self._temps, self._topks, self._seeds]
        return self._decode_step.lower(*args)

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    @property
    def n_preemptions(self) -> int:
        return self.scheduler.n_preemptions

    @property
    def kv_utilization(self) -> float:
        """Mean fraction of held KV page rows holding valid tokens across
        the decode steps so far (paged mode; 0.0 before any step).

        Tokens are counted per sequence but pages are distinct physical
        pages, so with the prefix cache on this can exceed 1.0: shared
        pages serve several sequences' tokens from one set of rows —
        the over-commit is exactly the sharing win."""
        if not self._util_page_tokens:
            return 0.0
        return self._util_tokens / self._util_page_tokens

    # -- admission (batched prefill) ---------------------------------------

    def _on_admit(self, seq: Sequence, now: float) -> None:
        """Admission bookkeeping: the queue wait, and on the sequence's
        first admission its ``queued`` span (arrival -> admission) on the
        ``requests`` track, so a request still running has one."""
        tele = self.telemetry
        arrive = seq.request.arrival_time
        tele.observe(self._m_queue_wait, now - (arrive or now))
        if tele.enabled and seq.admit_time is None:
            uid = seq.request.uid
            tele.tracer.add_span("queued", now if arrive is None else arrive,
                                 now, track="requests", tid=uid,
                                 args={"uid": uid})
        seq.admit_time = now

    def _first_token(self, seq: Sequence, t: float) -> None:
        """``seq`` sampled its first token at ``t``: TTFT and the
        ``first_token`` instant (a resumed sequence keeps its first)."""
        if seq.first_token_time is not None:
            return
        seq.first_token_time = t
        self.n_prompt_tokens += int(seq.request.prompt.size)
        tele = self.telemetry
        tele.observe(self._m_ttft, t - (seq.request.arrival_time or t))
        tele.instant("first_token", ts=t, track="requests",
                     tid=seq.request.uid)

    def _admit(self, group: Seq[ScheduledSeq]) -> List[RequestOutput]:
        tele = self.telemetry
        G, P = len(group), self.ec.prefill_batch
        bucket = group[0].bucket
        with tele.span("prefill", batch=G, bucket=bucket) as sp:
            now = time.perf_counter()
            with tele.span("prepare"):
                toks = np.zeros((P, bucket), np.int32)
                last = np.zeros((P,), np.int32)
                temps = np.zeros((P,), np.float32)
                topks = np.zeros((P,), np.int32)
                seeds = np.zeros((P,), np.int32)
                slots = np.zeros((P,), np.int32)
                prompts = [ss.seq.full_prompt for ss in group]
                for i, ss in enumerate(group):
                    spr = ss.request.sampling
                    n = prompts[i].size
                    toks[i, :n] = prompts[i]
                    last[i] = n - 1
                    temps[i], topks[i], seeds[i] = (spr.temperature,
                                                    spr.top_k, spr.seed)
                    slots[i] = ss.slot
                if self.paged:
                    # padded rows keep all-zero page tables: their KV
                    # scatters into the reserved sink page, so the insert
                    # needs no masking and every bucket compiles exactly
                    # one (P, bucket) prefill.
                    rows = self.scheduler.page_table_rows(list(group),
                                                          bucket)
                    dest = np.zeros((P, rows.shape[1]), np.int32)
                    dest[:G] = rows
                else:
                    # pad rows beyond G with copies of row 0: identical KV
                    # scattered to the same slot is a harmless repeat
                    # write.
                    for i in range(G, P):
                        toks[i], last[i], slots[i] = toks[0], last[0], \
                            slots[0]
                    dest = slots
            for ss in group:
                self._on_admit(ss.seq, now)
            first_tok, kv = self._run(("prefill", bucket),
                                      self._prefill_step, (self.params,),
                                      [toks, last, temps, topks, seeds])
            self._cache = self._run(("cache_insert", bucket),
                                    self._cache_insert, (self._cache, kv),
                                    [dest])
            self.n_prefill_calls += 1
            self.n_prefill_tokens += int(sum(p.size for p in prompts[:G]))
            with tele.span("sync"):
                first_np = np.asarray(first_tok)
            t_first = time.perf_counter()
            for i, ss in enumerate(group):
                seq = ss.seq
                self._first_token(seq, t_first)
                seq.generated.append(int(first_np[i]))
                self._slots[ss.slot] = seq
                spr = ss.request.sampling
                self._positions[ss.slot] = prompts[i].size
                self._cur_tok[ss.slot] = first_np[i]
                self._temps[ss.slot] = spr.temperature
                self._topks[ss.slot] = spr.top_k
                self._seeds[ss.slot] = spr.seed
        if tele.enabled:
            tele.observe(self._m_prefill_call, sp.end - sp.start)
        return self._complete_done([ss.slot for ss in group])

    # -- chunked prefill ---------------------------------------------------

    def _apply_cow(self) -> None:
        """Replay the scheduler's pending copy-on-write pairs on the
        device pool (src pages cloned onto fresh dst pages).  Batches are
        padded to a power of two with (0, 0) sink self-copies, bounding
        the compile count; dst pages are always freshly allocated, so no
        pair ever chains off another's destination."""
        if not self.paged:
            return
        copies = self.scheduler.take_cow_copies()
        if not copies:
            return
        n = 1
        while n < len(copies):
            n *= 2
        src = np.zeros((n,), np.int32)
        dst = np.zeros((n,), np.int32)
        for i, (s, d) in enumerate(copies):
            src[i], dst[i] = s, d
        with self.telemetry.span("cow", n_copies=len(copies)):
            self._cache = self._run(("copy", n), self._copy_pages,
                                    (self._cache,), [src, dst])

    def _advance_prefill_group(self, slots: List[int]) -> List[RequestOutput]:
        """Run one prompt chunk for a GROUP of mid-prefill sequences in a
        single batched ``prefill_chunk`` call (batch padded to a power of
        two; pad rows write only the sink page).  The final chunk of each
        sequence samples its first token (folded at the prompt's last
        position, exactly like whole prefill) and activates the slot.

        Coalescing is bit-exact vs one B=1 call per slot: rows' codes
        depend only on their own K/V, block tables are disjoint, and
        sample keys fold by (seed, position) — the
        ``prefill_chunk_calls_saved`` counter tallies the saved calls.
        """
        tele = self.telemetry
        # shared pages each chunk writes into must be copied first; a COW
        # preemption triggered by one slot can evict a peer from the group
        bounds: dict[int, tuple] = {}
        with tele.span("schedule"):
            for slot in slots:
                if slot not in self._prefilling:
                    continue
                seq = self._prefilling[slot]
                a = seq.prefill_progress
                b = min(a + self.chunk_tokens, seq.full_prompt.size)
                for vslot, vseq in self.scheduler.prepare_chunk_writes(
                        slot, a, b):
                    tele.instant("preempt", track="requests",
                                 tid=vseq.request.uid,
                                 args={"by": seq.request.uid, "cause": "cow"})
                    self._clear_slot(vslot)
                # apply per slot (not once for the group): a later prepare
                # may preempt an earlier slot and recycle its fresh COW dst
                # pages, so batching the pairs could alias two copies onto
                # one dst
                self._apply_cow()
                bounds[slot] = (a, b)
        live = [s for s in slots if s in self._prefilling and s in bounds]
        if not live:
            return []
        G = len(live)
        Bp = 1                              # power-of-two batch bucket:
        while Bp < G:                       # compile count stays O(log
            Bp *= 2                         # max_slots), not O(traffic)
        n_valid = sum(bounds[s][1] - bounds[s][0] for s in live)
        finished: List[RequestOutput] = []
        with tele.span("prefill_chunk", batch=G, tokens=n_valid) as sp:
            with tele.span("prepare"):
                host = self._chunk_inputs(live, bounds, Bp)
            tok, self._cache = self._run(("chunk", Bp), self._chunk_step,
                                         (self.params, self._cache), host)
            self.n_prefill_calls += 1
            self.n_prefill_tokens += n_valid
            if G > 1:
                self._m_chunk_saved.inc(G - 1)
            with tele.span("sync"):
                tok_np = np.asarray(tok)
            for i, slot in enumerate(live):
                seq = self._prefilling[slot]
                prompt = seq.full_prompt
                _, b = bounds[slot]
                seq.prefill_progress = b
                if b < prompt.size:
                    continue
                # final chunk: publish the full prompt pages, activate the
                # slot
                self.scheduler.on_prefill_complete(slot)
                seq.prefill_progress = None
                del self._prefilling[slot]
                first = int(tok_np[i])
                spr = seq.request.sampling
                self._first_token(seq, time.perf_counter())
                seq.generated.append(first)
                self._slots[slot] = seq
                self._positions[slot] = prompt.size
                self._cur_tok[slot] = first
                self._temps[slot] = spr.temperature
                self._topks[slot] = spr.top_k
                self._seeds[slot] = spr.seed
                # completed in place: the scheduler sees the same order of
                # registrations and releases as a B=1 call per slot
                finished.extend(self._complete_done([slot]))
        if tele.enabled:
            tele.observe(self._m_chunk_call, sp.end - sp.start)
            if self._slots:
                # decode was live while this chunk ran: interleaved
                # prefill work, the decode-stall currency
                self._m_tok_prefill_step.inc(n_valid)
        return finished

    def _chunk_inputs(self, live: List[int], bounds: dict,
                      Bp: int) -> list:
        """The numpy inputs of one coalesced ``prefill_chunk`` call for
        the slots ``live`` (rows beyond them are sink-page padding)."""
        C = self.chunk_tokens
        page = self.ec.page_size
        tables_all = np.asarray(self.scheduler.block_tables)
        toks = np.zeros((Bp, C), np.int32)
        positions = np.zeros((Bp, C), np.int32)
        write_pages = np.zeros((Bp, C), np.int32)  # pad rows -> sink page 0
        write_rows = np.zeros((Bp, C), np.int32)
        tables = np.zeros((Bp, tables_all.shape[1]), np.int32)
        last_idx = np.zeros((Bp,), np.int32)
        last_pos = np.zeros((Bp,), np.int32)
        temps = np.zeros((Bp,), np.float32)
        topks = np.zeros((Bp,), np.int32)
        seeds = np.zeros((Bp,), np.int32)
        for i, slot in enumerate(live):
            seq = self._prefilling[slot]
            prompt = seq.full_prompt
            a, b = bounds[slot]
            valid = b - a
            toks[i, :valid] = prompt[a:b]
            positions[i] = a + np.arange(C)
            row = tables_all[slot]
            write_pages[i, :valid] = row[positions[i, :valid] // page]
            write_rows[i, :valid] = positions[i, :valid] % page
            tables[i] = row
            sp = seq.request.sampling
            last_idx[i] = valid - 1
            last_pos[i] = prompt.size - 1
            temps[i], topks[i], seeds[i] = (sp.temperature, sp.top_k,
                                            sp.seed)
        return [toks, positions, write_pages, write_rows, tables, last_idx,
                last_pos, temps, topks, seeds]

    # -- decode ------------------------------------------------------------

    def _decode_inputs(self):
        """(batch bucket, numpy inputs after params and pool, row of each
        active slot or None when rows are slots) of the decode step."""
        if not self.paged:
            return self.ec.max_slots, [
                self._cur_tok, self._positions, self._temps, self._topks,
                self._seeds], None
        self._util_tokens += self.scheduler.tokens_in_use
        self._util_page_tokens += (self.scheduler.pages_in_use
                                   * self.ec.page_size)
        active = sorted(self._slots)
        Bb = self._decode_bucket(len(active))
        if self.ec.bucket_decode and active and Bb < self.ec.max_slots:
            # gather the active rows into the bucket; pad rows carry
            # zero block tables, so their scatter lands in the sink
            # page exactly like an inactive slot's in the full batch
            bt = np.asarray(self.scheduler.block_tables)
            rows = np.asarray(active, np.int32)
            host = [np.zeros(Bb, self._cur_tok.dtype),
                    np.zeros(Bb, self._positions.dtype),
                    np.zeros((Bb, bt.shape[1]), bt.dtype),
                    np.zeros(Bb, self._temps.dtype),
                    np.zeros(Bb, self._topks.dtype),
                    np.zeros(Bb, self._seeds.dtype)]
            for dst, src in zip(host, (self._cur_tok, self._positions, bt,
                                       self._temps, self._topks,
                                       self._seeds)):
                dst[:rows.size] = src[rows]
            self.n_bucketed_steps += 1
            return Bb, host, {slot: i for i, slot in enumerate(active)}
        block_tables = self.scheduler.block_tables
        if self._prefilling:
            # mid-prefill slots are inactive in the decode step, but it
            # still scatters their (zero) row-0 write — point those rows
            # at the sink so real (possibly shared) pages are never
            # touched
            block_tables = block_tables.copy()
            block_tables[list(self._prefilling)] = 0
        return self.ec.max_slots, [
            self._cur_tok, self._positions, block_tables, self._temps,
            self._topks, self._seeds], None

    def _decode_active(self) -> List[RequestOutput]:
        tele = self.telemetry
        n_active = len(self._slots)
        advanced: List[int] = []
        with tele.span("decode", batch=n_active) as sp:
            with tele.span("prepare"):
                bucket, host, row_of = self._decode_inputs()
            next_tok, self._cache = self._run(
                ("decode", bucket), self._decode_step,
                (self.params, self._cache), host)
            self.n_decode_steps += 1
            with tele.span("sync") as sync:
                next_np = np.asarray(next_tok)   # the step is done
            for slot in list(self._slots):
                seq = self._slots[slot]
                tok = next_np[slot if row_of is None else row_of[slot]]
                seq.generated.append(int(tok))
                self._positions[slot] += 1
                self._cur_tok[slot] = tok
                advanced.append(slot)
        if tele.enabled:
            t1 = sync.end
            tele.observe(self._m_decode_step, t1 - sp.start)
            tele.observe(self._m_batch, n_active)
            self._m_tok_decode.inc(n_active)
            if self._last_decode_end is not None:
                # gap between consecutive sampled tokens — includes any
                # scheduling/COW/chunked-prefill work between the steps,
                # which is exactly what a waiting client experiences
                tele.observe(self._m_itl, t1 - self._last_decode_end)
            self._last_decode_end = t1
        return self._complete_done(advanced)

    def _decode_bucket(self, n_active: int) -> int:
        """Power-of-two decode batch bucket for an active-slot count:
        floor ``_DECODE_BUCKET_MIN``, cap ``max_slots``."""
        b = _DECODE_BUCKET_MIN
        while b < n_active:
            b *= 2
        return min(b, self.ec.max_slots)

    def _finish_reason(self, slot: int) -> Optional[str]:
        seq = self._slots[slot]
        sp = seq.request.sampling
        if sp.stop_token >= 0 and seq.generated[-1] == sp.stop_token:
            return "stop"
        if len(seq.generated) >= sp.max_new_tokens:
            return "length"
        if not self.paged and self._positions[slot] >= self.ec.max_len:
            return "evicted"       # slot region exhausted; terminal (legacy)
        return None

    def _clear_slot(self, slot: int) -> None:
        self._slots.pop(slot, None)
        self._prefilling.pop(slot, None)
        self._positions[slot] = 0
        self._cur_tok[slot] = 0
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._seeds[slot] = 0

    def _complete_done(self, slots: List[int]) -> List[RequestOutput]:
        """Complete, in order, those of ``slots`` whose sequence has
        finished, under one ``complete`` span."""
        done = [(slot, r) for slot in slots
                if (r := self._finish_reason(slot))]
        if not done:
            return []
        with self.telemetry.span("complete", n=len(done)):
            return [self._complete(slot, r) for slot, r in done]

    def _complete(self, slot: int, reason: str) -> RequestOutput:
        seq = self._slots[slot]
        self.scheduler.complete(slot, evicted=(reason == "evicted"))
        self._clear_slot(slot)
        now = time.perf_counter()
        arrive = seq.request.arrival_time or seq.admit_time
        tele = self.telemetry
        if tele.enabled:
            tele.observe(self._m_e2e, now - arrive)
            tele.registry.counter(f"requests_finished_{reason}").inc()
            self._emit_lifecycle(seq, arrive, now, reason)
        return RequestOutput(
            uid=seq.request.uid, prompt=seq.request.prompt,
            token_ids=list(seq.generated), finish_reason=reason,
            ttft_s=(seq.first_token_time or now) - arrive,
            latency_s=now - arrive, n_preempts=seq.n_preempts)

    def _emit_lifecycle(self, seq: Sequence, arrive: float, finish: float,
                        reason: str) -> None:
        """Request-lifecycle spans on the ``requests`` track (tid = uid),
        written at completion: prefill [admit, first token], decode
        [first token, finish].  (``queued`` is written at the first
        admission and ``first_token`` when it is sampled.)  After
        preempt/resume, admit/first reflect the *last* admission; the
        preempt ``instant`` markers in between tell the story."""
        tr = self.telemetry.tracer
        uid = seq.request.uid
        admit = min(max(seq.admit_time or arrive, arrive), finish)
        first = min(max(seq.first_token_time or finish, admit), finish)
        tr.add_span("prefill", admit, first, track="requests", tid=uid,
                    args={"prompt_tokens": int(seq.request.prompt.size),
                          "cache_hit_tokens": seq.cache_hit_tokens})
        tr.add_span("decode", first, finish, track="requests", tid=uid,
                    args={"new_tokens": len(seq.generated),
                          "n_preempts": seq.n_preempts,
                          "finish_reason": reason})

    # -- main loop ---------------------------------------------------------

    def step(self) -> List[RequestOutput]:
        """One engine iteration: admit every admissible prefill group
        (chunked mode only claims slots/pages — compute is spread over
        later steps), advance one prompt chunk per mid-prefill slot,
        grow/preempt/copy pages for the coming decode writes (paged
        mode), then advance all active slots one decode step.

        Spans (``step`` and its children): ``schedule`` (admission,
        prefix-cache lookup, page growth, preemption), ``prefill`` /
        ``prefill_chunk`` and ``decode`` (each split into ``prepare``,
        ``upload``, ``dispatch`` and ``sync``), ``cow``, ``complete``."""
        tele = self.telemetry
        self.n_steps += 1
        finished: List[RequestOutput] = []
        with tele.span("step"):
            while True:
                with tele.span("schedule"):
                    group = self.scheduler.schedule()
                    if group and self.chunked:
                        now = time.perf_counter()
                        for ss in group:
                            self._on_admit(ss.seq, now)
                            ss.seq.prefill_progress = ss.seq.cache_hit_tokens
                            self._prefilling[ss.slot] = ss.seq
                if not group:
                    break
                if not self.chunked:
                    finished.extend(self._admit(group))
            if self._prefilling:
                # one chunk for EVERY mid-prefill slot, oldest first: the
                # decode stall per step stays bounded by
                # n_prefilling * chunk_tokens (the chunk size is the policy
                # knob), while a whole admission wave advances together
                # instead of serializing one sequence per step
                order = sorted(self._prefilling,
                               key=lambda s: self._prefilling[s].order)
                if self.ec.coalesce_prefill:
                    # ...and the whole wave shares ONE batched chunk call
                    finished.extend(self._advance_prefill_group(order))
                else:
                    for slot in order:
                        if slot in self._prefilling:  # not preempted by a peer
                            finished.extend(
                                self._advance_prefill_group([slot]))
            if self.paged and self._slots:
                with tele.span("schedule"):
                    for slot, seq in self.scheduler.ensure_decode_pages(
                            writing=set(self._slots)):
                        # sequence went back to the waiting queue with its
                        # tokens; only the device-side slot state is dropped
                        tele.instant("preempt", track="requests",
                                     tid=seq.request.uid,
                                     args={"cause": "pool_exhausted"})
                        self._clear_slot(slot)
                self._apply_cow()
            if self._slots:
                finished.extend(self._decode_active())
            else:
                # no decode ran: the next sampled token's gap is not an
                # inter-token latency (the stream was idle or pure-prefill)
                self._last_decode_end = None
        return finished

    def generate(self, requests: Seq[Request]) -> List[RequestOutput]:
        """Closed-set convenience: run a request list to completion."""
        for r in requests:
            self.submit(r)
        out: List[RequestOutput] = []
        while self.has_work:
            out.extend(self.step())
        return sorted(out, key=lambda o: o.uid)
