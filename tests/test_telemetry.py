"""Observability layer (serve/telemetry.py, the engine's spans and named
scopes, analysis/tracecheck.py): histogram bucket/percentile math against
numpy, span nesting and self time, metrics-snapshot stability across an
engine run that forces preemption and copy-on-write, request lifecycle
events, the engine's spans inside a JAX profiler trace, the scope map of
its compiled step programs, and the bit-parity contract that tracing,
annotations and scopes on/off yield identical token streams."""

import contextlib
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import tracecheck
from repro.configs import base as cb
from repro.models import model
from repro.models.lm import ModelOpts
from repro.serve import telemetry as tele
from repro.serve.engine import (AMBIGUOUS, Engine, EngineConfig, Request,
                                SamplingParams, hlo_op_scopes)


# ---------------------------------------------------------------------------
# Histograms
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_bucket_edge_construction(self):
        tb = tele.time_buckets(1e-5, 120.0, 1.15)
        assert list(tb) == sorted(tb)
        assert tb[0] == pytest.approx(1e-5)
        assert tb[-1] >= 120.0
        assert tele.linear_buckets(0.0, 1.0, 4) == (1.0, 2.0, 3.0, 4.0)

    def test_exact_aggregates_and_single_value_clamp(self):
        h = tele.Histogram("h", tele.time_buckets())
        h.observe(0.0137)
        assert h.count == 1
        assert h.sum == pytest.approx(0.0137)
        assert h.vmin == h.vmax == pytest.approx(0.0137)
        # clamping to [vmin, vmax] makes single-value histograms exact
        for q in (50, 95, 99):
            assert h.percentile(q) == pytest.approx(0.0137)

    def test_percentiles_vs_numpy_log_buckets(self):
        """Log buckets at factor 1.15 must land every percentile within
        one bucket (15% relative) of numpy's exact order statistic."""
        rng = np.random.default_rng(0)
        xs = rng.lognormal(mean=-4.0, sigma=1.0, size=5000)
        h = tele.Histogram("h", tele.time_buckets())
        for x in xs:
            h.observe(float(x))
        for q in (50, 90, 95, 99):
            exact = float(np.percentile(xs, q))
            got = h.percentile(q)
            assert got / exact == pytest.approx(1.0, abs=0.16), \
                f"p{q}: {got} vs numpy {exact}"
        assert h.count == xs.size
        assert h.sum == pytest.approx(float(xs.sum()))
        assert sum(h.counts) == h.count

    def test_percentiles_vs_numpy_linear_buckets(self):
        rng = np.random.default_rng(1)
        xs = rng.integers(0, 32, size=2000).astype(float)
        h = tele.Histogram("b", tele.linear_buckets(0.0, 1.0, 33))
        for x in xs:
            h.observe(x)
        for q in (50, 95, 99):
            assert abs(h.percentile(q) - float(np.percentile(xs, q))) <= 1.0

    def test_overflow_bucket_and_bounds(self):
        h = tele.Histogram("h", (1.0, 2.0))
        for v in (0.5, 1.5, 99.0):
            h.observe(v)
        assert h.counts == [1, 1, 1]          # last = implicit +inf bucket
        assert h.percentile(99) <= h.vmax
        assert h.percentile(1) >= h.vmin

    def test_snapshot_is_json_round_trippable(self):
        h = tele.Histogram("h", tele.time_buckets())
        h.observe(0.2)
        snap = json.loads(json.dumps(h.snapshot()))
        assert snap["count"] == 1 and snap["p50"] == pytest.approx(0.2)

    def test_registry_prometheus_exposition(self):
        reg = tele.MetricsRegistry()
        reg.counter("reqs", "requests").inc(3)
        reg.gauge("occ").set(1.5)
        h = reg.histogram("lat_s", (0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.to_prometheus(prefix="uniq_")
        assert "# TYPE uniq_reqs counter" in text
        assert "uniq_reqs 3" in text
        assert "# TYPE uniq_occ gauge" in text
        # histogram buckets must be cumulative, with the +Inf catch-all
        assert 'uniq_lat_s_bucket{le="0.1"} 1' in text
        assert 'uniq_lat_s_bucket{le="1"} 2' in text
        assert 'uniq_lat_s_bucket{le="+Inf"} 3' in text
        assert "uniq_lat_s_count 3" in text

    def test_registry_rejects_kind_mismatch(self):
        reg = tele.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def _fake_clock(start=0.0):
    t = [start]

    def clock():
        t[0] += 0.001
        return t[0]
    return clock


class TestTracer:
    def test_span_context_manager_records(self):
        tr = tele.Tracer(capacity=8, clock=_fake_clock())
        with tr.span("work", batch=3):
            pass
        s = next(tr.spans())
        assert s.name == "work" and s.args == {"batch": 3} and s.dur > 0

    def test_span_parents_and_self_time(self):
        tr = tele.Tracer(capacity=16, clock=_fake_clock())
        with tr.span("step"):
            with tr.span("decode"):
                with tr.span("sync"):
                    pass
            with tr.span("complete"):
                pass
        with tr.span("next"):
            pass
        tr.add_span("queued", 0.0, 0.5, track="requests", tid=3)
        by = {s.name: s for s in tr.spans()}
        assert by["step"].parent is None and by["next"].parent is None
        assert by["decode"].parent == by["step"].sid
        assert by["complete"].parent == by["step"].sid
        assert by["sync"].parent == by["decode"].sid
        assert by["queued"].parent is None
        assert len({s.sid for s in tr.spans()}) == 6
        self_t = tele.self_times(tr.spans())
        # the fake clock ticks 1 ms per read: each span's start and end
        # read it once, so a leaf lasts 1 ms and a parent's self time is
        # what its children leave of it
        assert self_t[by["sync"].sid] == pytest.approx(0.001)
        assert self_t[by["decode"].sid] == pytest.approx(
            by["decode"].dur - by["sync"].dur)
        assert self_t[by["step"].sid] == pytest.approx(
            by["step"].dur - by["decode"].dur - by["complete"].dur)
        assert self_t[by["queued"].sid] == pytest.approx(0.5)

    def test_annotation_wraps_span_with_ring_on_or_off(self):
        opened = []

        @contextlib.contextmanager
        def annotate(name):
            opened.append(("enter", name))
            yield
            opened.append(("exit", name))

        for enabled in (True, False):
            opened.clear()
            t = tele.Telemetry(enabled=enabled, trace_capacity=8,
                               annotate=annotate)
            with t.span("step"):
                with t.span("sync"):
                    pass
            assert opened == [("enter", "step"), ("enter", "sync"),
                              ("exit", "sync"), ("exit", "step")]
            assert t.tracer.n_spans_total == (2 if enabled else 0)

    def test_disabled_telemetry_records_nothing(self):
        t = tele.Telemetry(enabled=False, trace_capacity=8)
        with t.span("x"):
            t.inc(t.registry.counter("c"))
            t.observe(t.registry.histogram("h"), 1.0)
        assert t.registry.counter("c").value == 0
        assert t.registry.histogram("h").count == 0
        assert t.tracer.n_spans_total == 0


# ---------------------------------------------------------------------------
# Engine integration: stability under preemption + COW, bit-parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    cfg = cb.get_smoke("granite_3_8b")
    opts = ModelOpts(compute_dtype=jnp.float32, remat=False,
                     attn_chunked_min_len=1 << 30, kv_chunk=16,
                     ssd_chunk=8, ce_chunk=64)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, params, opts


def _cow_wave(vocab, uid0):
    """Three requests sharing a 12-token prefix (page_size 8 -> the
    shared tail is a *partial* page) with diverging suffixes: request 2+
    hit the registered prefix and must copy-on-write the partial page."""
    rng = np.random.default_rng(3)
    shared = rng.integers(0, vocab, 12).astype(np.int32)
    return [Request(uid=uid0 + i,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, vocab, 4).astype(np.int32)]),
                    sampling=SamplingParams(max_new_tokens=16))
            for i in range(3)]


def _preempt_wave(vocab, uid0):
    """Two sequences growing to 64 tokens (8 pages each) cannot coexist
    in an 11-usable-page pool: the newer one is preempted and resumed."""
    rng = np.random.default_rng(4)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(0, vocab, 8).astype(np.int32),
                    sampling=SamplingParams(max_new_tokens=56))
            for i in range(2)]


_EC = dict(max_slots=2, max_len=64, prefill_batch=2, min_bucket=8,
           cache_mode="paged", page_size=8, total_pages=12,
           prefix_cache=True, prefill_chunk=1)


class TestEngineTelemetry:
    def test_snapshot_stable_across_forced_preemption_and_cow(
            self, engine_setup):
        cfg, params, opts = engine_setup
        eng = Engine(params, cfg, opts, EngineConfig(**_EC))

        def run(uid0):
            outs = eng.generate(_cow_wave(cfg.vocab, uid0))
            outs += eng.generate(_preempt_wave(cfg.vocab, uid0 + 10))
            return outs

        outs1 = run(0)
        snap1 = eng.metrics_snapshot()
        # the run exercised both hard paths
        assert snap1["counters"]["cow_copies"] >= 1
        assert snap1["counters"]["preemptions"] >= 1
        assert snap1["counters"]["requests_finished_length"] == 5
        assert snap1["histograms"]["ttft_s"]["count"] == 5
        assert snap1["histograms"]["itl_s"]["count"] > 0
        assert snap1["meta"]["arch"] == cfg.name
        assert tracecheck.zero_metrics(
            snap1, ["decode_steps", "tokens_decoded", "prefill_tokens",
                    "cow_copies", "preemptions", "ttft_s", "itl_s",
                    "queue_wait_s", "e2e_latency_s"]) == []
        names = {s.name for s in eng.telemetry.tracer.spans()}
        assert {"step", "schedule", "decode", "prefill_chunk", "cow",
                "complete", "prepare", "upload", "dispatch", "sync",
                "queued"} <= names
        # snapshot must be JSON-stable (sorted keys, plain scalars)
        assert json.loads(json.dumps(snap1, sort_keys=True))

        # identical replay from a clean engine state: every event count
        # must reproduce exactly (timings vary; event structure may not)
        eng.flush_prefix_cache()
        eng.reset_stats()
        outs2 = run(100)
        snap2 = eng.metrics_snapshot()
        assert snap1["counters"] == snap2["counters"]
        assert [len(o.token_ids) for o in outs1] == \
            [len(o.token_ids) for o in outs2]
        for name, h in snap1["histograms"].items():
            assert snap2["histograms"][name]["count"] == h["count"], name
        # decode_batch is wall-clock-free: full bucket equality
        assert snap1["histograms"]["decode_batch"]["counts"] == \
            snap2["histograms"]["decode_batch"]["counts"]

    def test_running_request_has_queued_span(self, engine_setup):
        """``queued`` is written at admission: a request still decoding
        has it, and none of the spans written at completion."""
        cfg, params, opts = engine_setup
        eng = Engine(params, cfg, opts, EngineConfig(**_EC))
        rng = np.random.default_rng(5)
        req = Request(uid=41, prompt=rng.integers(0, cfg.vocab, 12)
                      .astype(np.int32),
                      sampling=SamplingParams(max_new_tokens=40))
        eng.submit(req)
        while not eng.scheduler.running() or \
                not eng.scheduler.running()[0].generated:
            assert not eng.step()
        mine = [s for s in eng.telemetry.tracer.spans()
                if s.track == "requests" and s.tid == 41]
        assert [s.name for s in mine] == ["queued"]
        q = mine[0]
        assert q.start == req.arrival_time
        assert q.start + q.dur == eng.scheduler.running()[0].admit_time

    def test_first_token_instant_matches_ttft(self, engine_setup):
        cfg, params, opts = engine_setup
        eng = Engine(params, cfg, opts, EngineConfig(**_EC))
        reqs = _cow_wave(cfg.vocab, 0) + _preempt_wave(cfg.vocab, 10)
        outs = {o.uid: o for o in eng.generate(reqs)}
        assert eng.n_preemptions >= 1
        firsts = [i for i in eng.telemetry.tracer.instants()
                  if i.name == "first_token"]
        # one per request, resumed ones included, at the step that
        # sampled it
        assert sorted(i.tid for i in firsts) == sorted(outs)
        for i in firsts:
            assert i.track == "requests"
            req = next(r for r in reqs if r.uid == i.tid)
            assert i.ts - req.arrival_time == pytest.approx(
                outs[i.tid].ttft_s, abs=1e-9)

    def test_op_scopes_of_the_step_programs(self, engine_setup):
        """Every projection dot and the head's logits dot map to
        ``matmul``, the attention einsums to ``attention``; the scan's
        own slicing keeps no scope; every name is an instruction of the
        compiled program; a name two buckets scope differently is
        ambiguous."""
        cfg, params, opts = engine_setup
        eng = Engine(params, cfg, opts, EngineConfig(**_EC))
        rng = np.random.default_rng(6)
        # two equal prompts admitted together: chunk and decode batch 2
        eng.generate([Request(uid=i, prompt=rng.integers(
            0, cfg.vocab, 16).astype(np.int32),
            sampling=SamplingParams(max_new_tokens=4)) for i in range(2)])
        assert sorted(eng._dispatched) == [("chunk", 2), ("decode", 2)]
        texts = {k: fn.lower(*args).compile().as_text()
                 for k, (fn, args) in eng._dispatched.items()}
        scopes = eng.op_scopes()
        assert set(scopes) == {"jit_chunk_fn", "jit_decode_paged"}
        for key, text in texts.items():
            module, own = hlo_op_scopes(text)
            assert own == scopes[module]
            names = set(re.findall(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s",
                                   text, re.M))
            assert set(own) == names
            dots = [(m.group(1), m.group(2)) for m in re.finditer(
                r"^\s*(?:ROOT\s+)?%?(\S+) = \S+ dot\(.*op_name=\"([^\"]*)",
                text, re.M)]
            einsum = [n for n, op in dots if "->" in op]
            proj = [n for n, op in dots if "->" not in op]
            assert len(einsum) == 2 and len(proj) == 8, key
            assert {own[n] for n in einsum} == {"attention"}
            assert {own[n] for n in proj} == {"matmul"}
            assert {"layer", "kv_write", "embed", "head", "sample",
                    ""} <= set(own.values())
            assert all(own[n] == "" for n, op in re.findall(
                r"^\s*(?:ROOT\s+)?%?(\S+) = .*op_name=\"([^\"]*)", text,
                re.M) if op.endswith("while/body/dynamic_slice"))
        # a second chunk bucket: names whose scope differs become AMBIGUOUS
        eng.generate([Request(uid=9, prompt=rng.integers(
            0, cfg.vocab, 16).astype(np.int32),
            sampling=SamplingParams(max_new_tokens=2))])
        fn, args = eng._dispatched[("chunk", 1)]
        _, one = hlo_op_scopes(fn.lower(*args).compile().as_text())
        _, two = hlo_op_scopes(texts[("chunk", 2)])
        merged = eng.op_scopes()["jit_chunk_fn"]
        assert set(merged) == set(one) | set(two)
        for n, scope in merged.items():
            both = {d[n] for d in (one, two) if n in d}
            assert scope == (both.pop() if len(both) == 1 else AMBIGUOUS)
        assert AMBIGUOUS in merged.values()

    @pytest.mark.parametrize("variant", ["telemetry_off", "annotations_on",
                                         "scopes_off"])
    def test_tracing_on_off_token_streams_bit_identical(
            self, engine_setup, default_streams, monkeypatch, variant):
        """The acceptance contract: telemetry, profiler annotations and
        named scopes must never perturb device work.  Sampled
        (temperature > 0) streams through the forced-preemption config
        are compared token by token against the default engine."""
        cfg, params, opts = engine_setup
        ec = dict(telemetry=variant != "telemetry_off",
                  profile_annotations=variant == "annotations_on")
        if variant == "scopes_off":
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
        toks, eng = _parity_run(engine_setup, **ec)
        assert toks == default_streams
        if variant == "telemetry_off":
            assert eng.telemetry.tracer.n_spans_total == 0
            # disabled telemetry also records no metrics
            off = eng.metrics_snapshot()
            assert off["histograms"]["ttft_s"]["count"] == 0
        else:
            assert eng.telemetry.tracer.n_spans_total > 0
        if variant == "scopes_off":
            assert set(eng.op_scopes()["jit_decode_paged"].values()) == {""}


def _parity_run(engine_setup, **ec):
    cfg, params, opts = engine_setup
    eng = Engine(params, cfg, opts, EngineConfig(**_EC, **ec))
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    sampling=SamplingParams(max_new_tokens=24,
                                            temperature=0.7, seed=100 + i))
            for i in range(3)]
    outs = eng.generate(reqs)
    return {o.uid: o.token_ids for o in outs}, eng


@pytest.fixture(scope="module")
def default_streams(engine_setup):
    return _parity_run(engine_setup)[0]


# ---------------------------------------------------------------------------
# The engine's spans in a JAX profiler trace
# ---------------------------------------------------------------------------

def _profile_steps(engine_setup, tmp_dir, annotate: bool):
    """Host events of a profiler trace of three engine steps: {line name:
    [(start_ns, end_ns, name)]} of the host plane."""
    from jax.profiler import ProfileData
    cfg, params, opts = engine_setup
    eng = Engine(params, cfg, opts,
                 EngineConfig(**_EC, profile_annotations=annotate))
    rng = np.random.default_rng(8)
    eng.generate([Request(uid=-1, prompt=rng.integers(0, cfg.vocab, 12)
                          .astype(np.int32),
                          sampling=SamplingParams(max_new_tokens=2))])
    for i in range(2):
        eng.submit(Request(uid=i, prompt=rng.integers(0, cfg.vocab, 12)
                           .astype(np.int32),
                           sampling=SamplingParams(max_new_tokens=8)))
    with jax.profiler.trace(str(tmp_dir)):
        for _ in range(3):
            eng.step()
    path, = glob.glob(f"{tmp_dir}/plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
    return lines


def test_profiler_trace_nests_engine_spans(engine_setup, tmp_path):
    lines = _profile_steps(engine_setup, tmp_path, annotate=True)
    holding = [ln for ln, evs in lines.items()
               if any(n == "engine.step" for _, _, n in evs)]
    assert len(holding) == 1 and holding[0].startswith("python")
    evs = lines[holding[0]]
    steps = [(a, b) for a, b, n in evs if n == "engine.step"]
    assert len(steps) == 3
    for name in ("engine.schedule", "engine.prepare", "engine.upload",
                 "engine.dispatch", "engine.sync"):
        inner = [(a, b) for a, b, n in evs if n == name]
        assert inner, name
        assert all(any(sa <= a and b <= sb for sa, sb in steps)
                   for a, b in inner), name
    # the phases of the Python thread's steps, each nested in its step
    assert {n for _, _, n in evs if n.startswith("engine.")} >= {
        "engine.step", "engine.schedule", "engine.prefill_chunk",
        "engine.prepare", "engine.upload", "engine.dispatch", "engine.sync"}


def test_annotations_off_add_no_engine_events(engine_setup, tmp_path):
    lines = _profile_steps(engine_setup, tmp_path, annotate=False)
    assert lines, "the profile holds the host plane"
    assert not [n for evs in lines.values() for _, _, n in evs
                if n.startswith("engine.")]


def test_tracecheck_reads_the_served_exports(engine_setup, tmp_path):
    """The CI gate: nonzero metrics in the snapshot and ``engine.step``
    in the profiler trace directory."""
    cfg, params, opts = engine_setup
    _profile_steps(engine_setup, tmp_path / "on", annotate=True)
    _profile_steps(engine_setup, tmp_path / "off", annotate=False)
    assert tracecheck.host_span_counts(str(tmp_path / "on"))[
        "engine.step"] == 3
    assert tracecheck.host_span_counts(str(tmp_path / "off"))[
        "engine.step"] == 0
    eng = Engine(params, cfg, opts, EngineConfig(**_EC))
    eng.generate(_cow_wave(cfg.vocab, 0))
    metrics = tmp_path / "m.json"
    eng.telemetry.write_metrics(str(metrics), eng.config_meta())
    assert tracecheck.main(["--metrics", str(metrics), "--require-nonzero",
                            "decode_steps,ttft_s,cache_hit_pages",
                            "--trace-dir", str(tmp_path / "on"),
                            "--require-span", "engine.step"]) == 0
    assert tracecheck.main(["--metrics", str(metrics), "--require-nonzero",
                            "preemptions,no_such_metric"]) == 1
    assert tracecheck.main(["--trace-dir", str(tmp_path / "off"),
                            "--require-span", "engine.step"]) == 1
