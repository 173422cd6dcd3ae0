#!/usr/bin/env python3
"""Record the small trace that ``test_scopes.py`` reads, on one TPU:

    python3 bench/tests/record_trace.py <out_dir>

A smoke-width engine cut from the sessions cell's configuration (bf16, kv16,
8-token pages, 1-page chunks, 4 slots), its profile annotations on, is warmed
as the harness warms it; then a ``bench.window`` annotation holds engine
steps, each in ``bench.step``, with a ``bench.wait`` sleep between some,
under the profiler: three prompts admitted together, then a fourth, so the
chunk program runs at two batch buckets.  Writes ``engine_v5e.xplane.pb``,
the engine's ``op_scopes()`` as ``engine_v5e.scopes.json`` and the window's
decode steps and prefill tokens as ``engine_v5e.counts.json`` to
``<out_dir>``.
"""

from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

from bench import run  # noqa: E402
from bench.tests.conftest import SMOKE_SIZES, SOURCE_CONFIG  # noqa: E402

SERVING = dict(max_slots=4, max_len=128, page_size=8, prefill_chunk=1,
               pool_bytes=64 * 8192)


def main(out_dir: str) -> None:
    import jax
    from repro.serve.engine import Request, SamplingParams
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py records a TPU trace")
    conf = {c["name"]: c for c in json.loads(
        (run.ROOT / "BENCHMARK.json").read_text())["configs"]}
    config = json.loads((run.ROOT / conf[SOURCE_CONFIG]["file"]).read_text())
    config.update(SMOKE_SIZES)
    config["serving"].update(SERVING)
    eng = run.build(config, seed=3, counts=run.config_module(config, "work"),
                    annotate=True)
    run.warm(eng, config, None, seed=3)
    rng = np.random.default_rng(3)

    def submit(uid, n):
        eng.submit(Request(uid, rng.integers(0, config["vocab_size"], n)
                           .astype(np.int32),
                           SamplingParams(max_new_tokens=6)))
    for uid in (1, 2, 3):
        submit(uid, 20)
    d0, p0 = eng.n_decode_steps, eng.n_prefill_tokens
    trace_dir = tempfile.mkdtemp(prefix="record_trace_")
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(10):
            if i == 4:
                submit(4, 12)
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
            if i % 3 == 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.002)
    jax.profiler.stop_trace()
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    shutil.copy(path, out / "engine_v5e.xplane.pb")
    shutil.rmtree(trace_dir, ignore_errors=True)
    (out / "engine_v5e.scopes.json").write_text(
        json.dumps(eng.op_scopes(), sort_keys=True))
    (out / "engine_v5e.counts.json").write_text(json.dumps(
        {"decode_steps": eng.n_decode_steps - d0,
         "prefill_tokens": eng.n_prefill_tokens - p0,
         "dispatched": sorted(map(list, eng._dispatched))}))
    print(f"recorded {path} -> {out}")


if __name__ == "__main__":
    main(sys.argv[1])
