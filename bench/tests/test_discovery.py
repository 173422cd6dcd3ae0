"""A later change adds a traffic mix or a per-layer metric as files of its
own; the harness finds them by name with no existing file edited."""

import json

import pytest

from bench import run, traffic


def test_a_new_mix_is_found_by_name(tmp_path):
    mix = json.loads((run.BENCH / "traffic" / "sessions.json").read_text())
    mix["output"] = dict(median=4, sigma=0.1, min=2, max=8)
    (tmp_path / "throwaway.json").write_text(json.dumps(mix))
    got = traffic.load("throwaway", tmp_path)
    assert got["output"]["median"] == 4
    gen = traffic.generator("throwaway", tmp_path)
    assert gen is traffic.sessions
    sess = gen(got, 7, 100, 16)
    assert all(2 <= t.output <= 8 for s in sess for t in s.turns)


def test_a_new_arrival_kind_is_a_generator_of_the_mix(tmp_path):
    """A mix whose sessions arrive in on/off bursts brings the arrivals in
    ``<mix>.py``; the sizes stay the general generator's."""
    mix = json.loads((run.BENCH / "traffic" / "sessions.json").read_text())
    mix["arrival"] = dict(kind="bursts", on_s=1.0, off_s=3.0, per_burst=4)
    (tmp_path / "bursty.json").write_text(json.dumps(mix))
    (tmp_path / "bursty.py").write_text(
        "import dataclasses\n"
        "from bench import traffic\n\n"
        "def sessions(mix, seed, vocab, n):\n"
        "    a = mix['arrival']\n"
        "    flat = dict(mix, arrival=dict(kind='poisson', rate_per_s=1.0))\n"
        "    out = traffic.sessions(flat, seed, vocab, n)\n"
        "    period = a['on_s'] + a['off_s']\n"
        "    return [dataclasses.replace(s, arrival_s=(i // a['per_burst'])\n"
        "            * period + (i % a['per_burst']) * a['on_s']\n"
        "            / a['per_burst']) for i, s in enumerate(out)]\n")
    with pytest.raises(ValueError, match="generator of its own"):
        traffic.sessions(mix, 3, 100, 8)
    got = traffic.generator("bursty", tmp_path)(
        traffic.load("bursty", tmp_path), 3, 100, 8)
    assert [s.arrival_s for s in got] == [0.0, 0.25, 0.5, 0.75,
                                          4.0, 4.25, 4.5, 4.75]
    assert got[0].prefix.size == mix["system_prompt"]["tokens"]


def test_a_new_metric_reader_is_found_by_name(tmp_path):
    (tmp_path / "throwaway_metric.py").write_text(
        "def read(r):\n    return 2.0 * r.x\n")

    class R:
        x = 21.0
    assert run.layer_reader("throwaway_metric", tmp_path)(R) == 42.0


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(run.layer_reader(m["name"]))
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
