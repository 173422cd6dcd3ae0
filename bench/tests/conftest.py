"""Shared fixtures of the benchmark's CPU tests: a checkout of its own
whose ``BENCHMARK.json`` names two smoke-width cells built from the real
configuration and mix files, so that a run drives every part of the harness
at a size the CPU holds."""

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SMOKE_SIZES = dict(hidden_size=64, intermediate_size=160, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   vocab_size=512)
# the real configuration and mix the smoke cells are cut from, and the real
# cell each stands for in the metrics' cell lists
SOURCE_CONFIG = "granite_3_8b.w16kv16.l20"
SOURCE_MIX = "sessions"
STANDS_FOR = {"granite_3_8b.w16kv16.l20.sessions": "smoke.sessions"}


def _spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config_file(name):
    """The file of configuration ``name`` of ``BENCHMARK.json``."""
    conf = {c["name"]: c for c in _spec()["configs"]}[name]
    return ROOT / conf["file"]


def _shrink_config(name, serving, max_gap):
    c = json.loads(config_file(SOURCE_CONFIG).read_text())
    c.update(name=name, **SMOKE_SIZES)
    c["serving"].update(serving)
    c["check"]["limit"] = max_gap
    return c


def _shrink_mix(**changes):
    m = json.loads((BENCH / "traffic" / f"{SOURCE_MIX}.json").read_text())
    m.update(changes)
    return m


@pytest.fixture(scope="session")
def smoke_root(tmp_path_factory):
    """A checkout with the cells ``smoke.batch`` (W4 kv8, closed loop that
    starts mid-answer) and ``smoke.sessions`` (W16 kv16, multi-turn
    sessions)."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "layers", root / "bench" / "layers")
    # the modules the configuration files name, found in the checkout
    for name in ("reference.py", "work.py"):
        shutil.copy(BENCH / name, root / "bench" / name)
    configs = {
        "smoke.w4kv8": _shrink_config(
            "smoke.w4kv8", dict(w_bits=4, kv_bits=8, max_slots=4, max_len=128,
                                page_size=8, prefill_chunk=2,
                                pool_bytes=64 * 1280), 0.5),
        "smoke.w16": _shrink_config(
            "smoke.w16", dict(max_slots=4, max_len=128, page_size=8,
                              prefill_chunk=1, pool_bytes=64 * 8192), 0.5),
    }
    for name, c in configs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(c))
    lognormal = dict(median=8, sigma=0.5, min=4, max=16)
    mixes = {
        "smoke_batch": _shrink_mix(
            arrival=dict(kind="closed", clients="max_slots"),
            system_prompt=None, turns=dict(min=1, max=1),
            first_tokens=dict(median=24, sigma=0.7, min=8, max=48),
            output=dict(median=8, sigma=0.5, min=4, max=16), think_s=0,
            max_context=128, steady_start=True, block=8,
            lead=dict(kind="first_tokens"), check_requests=3),
        "smoke_sessions": _shrink_mix(
            arrival=dict(kind="poisson", rate_per_s=8.0),
            system_prompt=dict(count=2, tokens=24, zipf_s=1.0),
            turns=dict(min=2, max=3), first_tokens=lognormal,
            turn_tokens=lognormal,
            output=dict(median=6, sigma=0.5, min=4, max=8), think_s=0.05,
            max_context=120, block=8, lead=dict(kind="seconds", s=0.5),
            check_requests=3),
    }
    for name, m in mixes.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(m))
    spec = _spec()
    spec["configs"] = [
        dict(reduced=[], why="smoke", name=name,
             source="https://huggingface.co/ibm-granite/granite-3.0-8b-base",
             file=f"bench/configs/{name}.json") for name in configs]
    spec["workloads"] = [
        dict(name="smoke.batch", config="smoke.w4kv8", traffic="smoke_batch",
             chips=1, why="smoke"),
        dict(name="smoke.sessions", config="smoke.w16",
             traffic="smoke_sessions", chips=1, why="smoke")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [STANDS_FOR[w] for w in m["workloads"]
                              if w in STANDS_FOR]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def cpu_devices(n):
    """Stands in for the harness's chip check on the CPU."""
    import jax
    return jax.devices()[:n]
