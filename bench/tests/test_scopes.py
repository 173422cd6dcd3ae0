"""The split of the step programs' device time by named scope and of the
device's idle time by host span (``bench/scopes.py``) and the five readers
built on it: exact arithmetic on a hand-made trace, then a small trace
recorded on a TPU v5e by ``record_trace.py`` with the engine's scope map
saved beside it."""

import json
import pathlib
import types

import pytest

from bench import run, scopes, trace, work
from bench.tests.conftest import SMOKE_SIZES, SOURCE_CONFIG, config_file

DATA = pathlib.Path(__file__).resolve().parent / "data"
READERS = ("decode_attn_ms", "decode_matmul_ms", "decode_unscoped_ms",
           "prefill_attn_ms_per_ktok", "idle_engine_ms_per_step")


class _Engine:
    def __init__(self, scope_map):
        self._map = scope_map

    def op_scopes(self):
        return self._map


def _reading(tr, scope_map, decode_steps, prefill_tokens):
    """The namespace ``bench/run.py`` hands the readers."""
    w = tr.window()
    eng = _Engine(scope_map) if scope_map is not None else object()
    return types.SimpleNamespace(
        trace=tr, window=w, window_s=w[1] - w[0],
        drv=types.SimpleNamespace(eng=eng),
        win=types.SimpleNamespace(decode_steps=decode_steps,
                                  counters={"prefill_tokens":
                                            prefill_tokens}))


def _read(name, r):
    return run.layer_reader(name)(r)


# -- a hand-made trace -------------------------------------------------------

def _made():
    modules = [(1.0, 3.0, "jit_decode_paged"), (4.0, 5.0, "jit_chunk_fn"),
               (6.0, 8.0, "jit_decode_paged")]
    ops = [(1.0, 3.0, "while.1"),               # a container: left out
           (1.0, 1.5, "fusion.1"), (1.5, 2.0, "fusion.2"),
           (2.0, 2.5, "copy.3"), (2.6, 3.0, "fusion.9"),
           (4.0, 4.5, "fusion.1"), (4.5, 5.0, "fusion.2"),
           (6.0, 7.0, "fusion.1"), (7.0, 7.5, "dot.4"),
           (7.5, 8.0, "fusion.2")]
    host = [(0.0, 10.0, "bench.window"),
            (0.5, 8.5, "bench.step"),
            (0.6, 8.4, "engine.step"),
            (0.6, 1.0, "engine.schedule"),
            (3.0, 3.8, "engine.sync"),
            (3.1, 3.7, "$builtins sleep"),       # a Python-tracer frame
            (5.0, 5.9, "engine.upload"),
            (9.0, 9.5, "bench.wait")]
    return trace.DeviceTrace(sorted(ops), sorted(modules), sorted(host))


DECODE_MAP = {"fusion.1": "matmul", "fusion.2": "attention", "copy.3": "",
              "dot.4": "dequant", "while.1": "layer"}
CHUNK_MAP = {"fusion.1": "kv_write", "fusion.2": "attention"}


def test_ops_are_split_by_the_module_they_lie_in():
    s = scopes.split_ops(_made(), (0.0, 10.0), "jit_decode_paged",
                         DECODE_MAP)
    assert s.runs == 2 and s.module_s == pytest.approx(4.0)
    assert s.seconds == pytest.approx({"matmul": 1.5, "attention": 1.0,
                                       "": 0.5, "(no name)": 0.4,
                                       "dequant": 0.5})
    assert s.covered_s == pytest.approx(3.9)    # 2.5-2.6 ran nothing
    assert s.mapped_s == pytest.approx(3.5)
    c = scopes.split_ops(_made(), (0.0, 10.0), "jit_chunk_fn", CHUNK_MAP)
    assert c.seconds == pytest.approx({"kv_write": 0.5, "attention": 0.5})


def test_idle_is_charged_to_the_innermost_engine_span():
    s = scopes.split_idle(_made(), (0.0, 10.0))
    assert s.steps == 1
    # gaps: 0-1 (midpoint 0.5 before engine.step opens: bench.step), 3-4
    # (engine.sync, through the tracer frame), 5-6 (engine.upload), 8-10
    # (midpoint 9: bench.wait)
    assert s.seconds == pytest.approx({"bench.step": 1.0,
                                       "engine.sync": 1.0,
                                       "engine.upload": 1.0,
                                       "bench.wait": 2.0})
    assert s.idle_s == pytest.approx(5.0)
    assert sum(s.seconds.values()) == pytest.approx(s.idle_s)
    assert s.of("engine.") == pytest.approx(2.0)


def test_readers_on_the_made_trace():
    r = _reading(_made(), {"jit_decode_paged": DECODE_MAP,
                           "jit_chunk_fn": CHUNK_MAP}, 2, 500)
    assert _read("decode_attn_ms", r) == pytest.approx(500.0)
    assert _read("decode_matmul_ms", r) == pytest.approx(1000.0)
    assert _read("decode_unscoped_ms", r) == pytest.approx(250.0)
    assert _read("prefill_attn_ms_per_ktok", r) == pytest.approx(1000.0)
    assert _read("idle_engine_ms_per_step", r) == pytest.approx(2000.0)


def test_readers_read_nothing_from_an_engine_without_scopes_or_spans():
    """The parent's engine has no ``op_scopes`` and opens no
    ``engine.step``: every reader returns None and none raises."""
    tr = _made()
    tr.host = [h for h in tr.host if not h[2].startswith("engine.")]
    r = _reading(tr, None, 2, 500)
    assert [_read(n, r) for n in READERS] == [None] * len(READERS)


# -- the recorded trace ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(str(DATA / "engine_v5e.xplane.pb"))
    scope_map = json.loads((DATA / "engine_v5e.scopes.json").read_text())
    counts = json.loads((DATA / "engine_v5e.counts.json").read_text())
    return tr, scope_map, counts


def test_recorded_decode_split_covers_and_maps(recorded):
    tr, scope_map, counts = recorded
    r = _reading(tr, scope_map, counts["decode_steps"],
                 counts["prefill_tokens"])
    s = scopes.program_split(r, "jit_decode_paged")
    assert s.runs == counts["decode_steps"]
    assert s.covered_s >= 0.9 * s.module_s
    assert s.mapped_s >= 0.95 * s.op_s
    per_step = [_read(n, r) for n in ("decode_attn_ms", "decode_matmul_ms",
                                      "decode_unscoped_ms")]
    assert all(v > 0 for v in per_step)
    others = sum(v for k, v in s.seconds.items()
                 if k not in ("attention", "matmul", "dequant", ""))
    total = sum(per_step) + 1e3 * others / counts["decode_steps"]
    assert total == pytest.approx(1e3 * s.op_s / counts["decode_steps"],
                                  rel=1e-9)
    # the model's own scopes all reach the device
    assert {"layer", "kv_write", "embed", "head", "sample"} <= set(s.seconds)


def test_recorded_chunk_split(recorded):
    tr, scope_map, counts = recorded
    r = _reading(tr, scope_map, counts["decode_steps"],
                 counts["prefill_tokens"])
    assert _read("prefill_attn_ms_per_ktok", r) > 0
    s = scopes.program_split(r, "jit_chunk_fn")
    assert s.covered_s >= 0.9 * s.module_s


def test_recorded_idle_split_accounts_for_every_idle_second(recorded):
    tr, scope_map, counts = recorded
    r = _reading(tr, scope_map, counts["decode_steps"],
                 counts["prefill_tokens"])
    s = scopes.idle_split(r)
    assert s.steps == 10
    assert sum(s.seconds.values()) == pytest.approx(
        r.window_s - trace.busy_seconds(tr, r.window), rel=1e-9)
    assert s.of("engine.") > 0 and s.of("bench.") > 0
    assert not [k for k in s.seconds if k.startswith("$")]
    assert _read("idle_engine_ms_per_step", r) == pytest.approx(
        1e3 * s.of("engine.") / 10)


@pytest.mark.parametrize("name", READERS)
def test_recorded_readers_read_the_same_without_python_frames(recorded, name):
    """The harness traces with the profiler's Python tracer off: its ``$``
    frames (most of the recorded trace's host events) feed no reader."""
    tr, scope_map, counts = recorded
    bare = trace.DeviceTrace(tr.ops, tr.modules,
                             [h for h in tr.host if not h[2].startswith("$")])
    assert len(bare.host) < len(tr.host) / 10
    full, without = (_read(name, _reading(t, scope_map, counts["decode_steps"],
                                          counts["prefill_tokens"]))
                     for t in (tr, bare))
    assert full is not None and without == full


class _Doubled:
    """A counts module of another architecture: twice the dense counts."""
    weight_bytes = staticmethod(work.weight_bytes)

    @staticmethod
    def decode_step(c, contexts):
        flops, nbytes = work.decode_step(c, contexts)
        return 2 * flops, 2 * nbytes


@pytest.mark.parametrize("scale", [1, 2], ids=["granite_file", "doubled"])
def test_recorded_decode_mfu_reads_the_configurations_counts(recorded,
                                                             scale):
    """``decode_mfu`` takes a step's operations and bytes from ``r.work``,
    the module the configuration names: the Granite file's reads what
    ``bench/work.py`` gives, and a module that counts twice the work reads
    twice the share."""
    from bench.tests.record_trace import SERVING
    tr, scope_map, counts = recorded
    config = json.loads(config_file(SOURCE_CONFIG).read_text())
    config.update(SMOKE_SIZES)
    config["serving"].update(SERVING)
    r = _reading(tr, scope_map, counts["decode_steps"],
                 counts["prefill_tokens"])
    # the recording kept no contexts: three rows, a fourth joining
    steps = [[21 + i] * 3 for i in range(4)] + \
        [[25 + i] * 3 + [13 + i] for i in range(4)]
    mid = (r.window[0] + r.window[1]) / 2
    r.drv.decode_ctx = [(mid, ctx) for ctx in steps]
    r.win.start, r.win.end = r.window
    r.config, r.peaks = config, work.peaks("TPU v5 lite")
    r.work = run.config_module(config, "work") if scale == 1 else _Doubled
    dev = trace.module_seconds(tr, r.window)["jit_decode_paged"]
    dense = 100.0 * sum(work.least_seconds(*work.decode_step(config, ctx),
                                           r.peaks)[0] for ctx in steps) / dev
    assert _read("decode_mfu", r) == pytest.approx(scale * dense, rel=1e-12)
