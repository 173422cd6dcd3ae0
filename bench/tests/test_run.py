"""Whole runs of the harness at smoke width on the CPU: the chip check is
replaced, everything else runs as on the chip (build, warm-up, lead,
window, reference check).  A fault planted in the timed path must turn
``correct`` false; the harness itself must refuse a machine without a
TPU."""

import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import run, trace
from bench.tests.conftest import ROOT, cpu_devices


def _run(root, cell, seed, *extra, traced=0):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "1.5", "--trace", str(traced), *extra],
                    require=cpu_devices, root=root)


@pytest.mark.parametrize("cell,e2e", [
    ("smoke.batch", {"out_tok_s", "tpot_ms", "itl_p95_ms", "setup_s"}),
    ("smoke.sessions", {"out_tok_s", "tpot_ms", "itl_p95_ms", "ttft_p90_ms",
                        "setup_s"})])
def test_a_sound_run_is_correct(smoke_root, cell, e2e, capsys):
    res = _run(smoke_root, cell, 2 ** 31 + 17)
    assert res["correct"], res
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1]) == res
    assert "limit" in out.err.strip().splitlines()[-1]
    assert " 0 programs compiled or loaded" in out.err    # all warmed


@pytest.mark.parametrize("cell", ["smoke.batch", "smoke.sessions"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        smoke_root, cell, monkeypatch):
    from repro.serve import engine
    sample = engine._sample_batch

    def altered(logits, keys, temps, top_ks):
        tok = sample(logits, keys, temps, top_ks)
        return (tok + 1 + jax.numpy.argmin(logits, -1)) % logits.shape[-1]
    monkeypatch.setattr(engine, "_sample_batch", altered)
    res = _run(smoke_root, cell, 5)
    assert not res["correct"]
    assert res["check"]["max_gap"]["value"] > \
        res["check"]["max_gap"]["limit"]


@pytest.mark.parametrize("cell", ["smoke.batch", "smoke.sessions"])
def test_the_control_runs(smoke_root, cell, capsys):
    """The control, kept at a size a test holds: the reference's int8 and
    fp8 streams put in the program's place.  Whether it fails is measured
    at the cell's size on the chip (PERF.md)."""
    res = _run(smoke_root, cell, 11, "--control", "1")
    err = capsys.readouterr().err
    line = next(ln for ln in err.splitlines() if ln.startswith("[control]"))
    assert "'int8'" in line and "'fp8'" in line and 'mean_gap' in line
    assert np.isfinite(res["check"]["max_gap"]["value"])


def test_the_harness_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        cell["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def _cpu_loader(host_names):
    """Stands in for ``trace.load`` on the CPU, whose trace has no TPU plane:
    the Python thread's host events as ``trace.load`` reads them (their names
    are kept in ``host_names``), and the CPU executor threads' events as the
    device's ops."""
    def load(path, device=0):
        from jax.profiler import ProfileData
        host, ops = [], []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                (host if line.name.startswith("python") else ops).extend(
                    (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    for e in line.events)
        host_names.extend(n for _, _, n in host)
        return trace.DeviceTrace(sorted(ops), [], sorted(host))
    return load


@pytest.mark.parametrize("answer_in_drain", [True, False])
def test_the_profilers_teardown_is_not_charged_to_the_drain(
        smoke_root, monkeypatch, capsys, answer_in_drain):
    """A traced run whose ``stop_trace`` outlasts ``MAX_DRAIN_S``, after a
    window whose engine makes no progress in its last 0.8 s, so that turns
    submitted then wait through the teardown: they answer in the drain that
    follows it.  With the engine's steps still no-ops in the drain they
    never answer, and the gate still counts them ``failed``."""
    from repro.serve.engine import Engine
    host_names, opened, torn_down = [], [], []
    start, stop, step = (jax.profiler.start_trace, jax.profiler.stop_trace,
                         Engine.step)
    v5e = run.work.peaks("TPU v5 lite")

    def start_trace(*args, **kwargs):
        opened.append(time.perf_counter())
        return start(*args, **kwargs)

    def slow_stop():
        stop()
        time.sleep(1.5)
        torn_down.append(True)

    def stalling_step(self):
        stalled = opened and time.perf_counter() > opened[0] + 0.7
        if stalled and not (torn_down and answer_in_drain):
            return []
        return step(self)
    monkeypatch.setattr(trace, "load", _cpu_loader(host_names))
    monkeypatch.setattr(run.work, "peaks", lambda kind: v5e)
    monkeypatch.setattr(run, "MAX_DRAIN_S", 1.0)
    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    monkeypatch.setattr(Engine, "step", stalling_step)
    res = _run(smoke_root, "smoke.sessions", 2 ** 31 + 29, traced=1)
    err = capsys.readouterr().err
    assert re.search(r"^\[trace\] stop_trace \d+\.\d\ds$", err, re.M)
    drain = re.search(r"^\[drain\] \+(\S+)s to \+(\S+)s after the window's "
                      r"end: (\d+) turns due", err, re.M)
    begin, end, waiting = float(drain[1]), float(drain[2]), int(drain[3])
    assert begin >= 1.5 and waiting > 0     # the teardown held turns back
    if answer_in_drain:
        assert res["correct"] and res["failed"] == 0, res
        assert end < begin + 1.0
    else:
        assert not res["correct"] and res["failed"] > 0, res
        assert res["check"]["failed"] == {"value": res["failed"], "limit": 0}
        assert end >= begin + 1.0
        assert "no first token" in err
    # the trace read: the engine's and the harness's spans, no Python frames
    assert not [n for n in host_names if n.startswith("$")]
    assert {"bench.window", "engine.step"} <= set(host_names)
