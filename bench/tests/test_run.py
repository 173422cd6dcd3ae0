"""Whole runs of the harness at smoke width on the CPU: the chip check is
replaced, everything else runs as on the chip (build, warm-up, lead,
window, reference check).  A fault planted in the timed path must turn
``correct`` false; the harness itself must refuse a machine without a
TPU."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import run
from bench.tests.conftest import ROOT, cpu_devices


def _run(root, cell, seed, *extra):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "1.5", "--trace", "0", *extra],
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
