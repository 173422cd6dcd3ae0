"""The reduction from a profiler trace to device metrics, on a small trace
recorded on a TPU v5e: three runs of a program holding the coded
paged-attention kernel and two matmuls, inside a ``bench.window`` span."""

import pathlib
import time

import pytest

from bench import trace
from bench.run import CompileLog

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(DATA / "small_v5e.xplane.pb"))


def test_window_and_clock_alignment(tr):
    w = tr.window()
    assert w is not None and w[1] > w[0]
    # the device clock lagged the host's: aligned, no op starts early
    assert tr.ops[0][0] >= w[0] - 1e-12
    assert len(tr.modules) == 3
    assert all(w[0] <= a and b <= w[1] for a, b, _ in tr.modules)


def test_busy_is_the_union_of_op_intervals(tr):
    w = tr.window()
    busy = trace.busy_seconds(tr, w)
    ops = trace.clip(tr.ops, w)
    assert busy <= sum(b - a for a, b, _ in ops) + 1e-12
    assert busy == pytest.approx(
        sum(b - a for a, b in trace.union((a, b) for a, b, _ in ops)))
    # every op of the program lies inside its module's runs
    assert busy <= sum(b - a for a, b, _ in tr.modules) + 1e-9
    assert 0 < busy < w[1] - w[0]


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_per_program_and_per_kernel_time(tr):
    w = tr.window()
    mods = trace.module_seconds(tr, w)
    assert set(mods) == {"jit_decode_paged"}
    assert trace.module_runs(tr, w, "jit_decode_paged") == 3
    sec, calls = trace.op_seconds(tr, w, "paged_quant_attention")
    assert calls == 3
    # the kernel takes most of each run, and no more than the runs
    assert 0.5 * mods["jit_decode_paged"] < sec < mods["jit_decode_paged"]
    top = trace.top_ops(tr, w)
    assert top[0][0] == "paged_quant_attention"


def test_idle_gaps_are_charged_to_host_spans(tr):
    w = tr.window()
    gaps = dict(trace.idle_gaps(tr, w))
    idle = (w[1] - w[0]) - trace.busy_seconds(tr, w)
    assert sum(gaps.values()) <= idle + 1e-12
    # the probe slept between runs: the sleep holds most of the idle time
    assert max(gaps, key=gaps.get) == "$time sleep"


def test_names():
    assert trace.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion.12"
    assert trace.module_name("jit_chunk_fn(123456)") == "jit_chunk_fn"
    assert trace.op_family("fusion.12") == "fusion"


def test_compiles_counted_only_inside_the_window():
    log = CompileLog()
    log.times[:] = []
    log._on("/jax/core/compile/backend_compile_duration")
    t0 = time.perf_counter()
    log._on("/jax/compilation_cache/cache_hits")
    log._on("/jax/core/compile/jaxpr_trace_duration")   # not a compile
    t1 = time.perf_counter()
    log._on("/jax/core/compile/backend_compile_duration")
    assert len(log.times) == 3
    assert log.between(t0, t1) == 1
