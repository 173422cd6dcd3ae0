"""A later change adds a traffic mix, a per-layer metric or a configuration
of another architecture as files of its own; the harness finds them by name
with no existing file edited."""

import json
import shutil

import pytest

from bench import run, traffic
from bench.tests.conftest import cpu_devices


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


# a configuration of another architecture: its file names a reference and a
# counts module of its own and sets an ArchConfig field; the stand-ins
# delegate to the dense decoder's (the smoke engine serves that), the
# reference adding ``PLANT`` to its widest gap
OTHER_REFERENCE = """from bench import reference

PLANT = {plant!r}


def gaps(config, seed, seqs, control=False):
    got = reference.gaps(config, seed, seqs, control=control)
    return dict(got, max_gap=got["max_gap"] + PLANT)
"""
OTHER_WORK = """from bench import work

decode_step = work.decode_step


def weight_bytes(c):
    return 123456789
"""


def _other_checkout(smoke_root, tmp_path, plant=0.0, **config):
    """A copy of the smoke checkout with the configuration ``smoke.other``
    and the cell ``smoke.other.sessions`` added as new files and new
    entries; returns the checkout and its files' bytes before the
    addition."""
    root = tmp_path / "checkout"
    shutil.copytree(smoke_root, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    arch = root / "bench" / "archs" / "other"
    arch.mkdir(parents=True)
    (arch / "reference.py").write_text(OTHER_REFERENCE.format(plant=plant))
    (arch / "work.py").write_text(OTHER_WORK)
    (arch / "empty.py").write_text("")
    (tmp_path / "outside.py").write_text(OTHER_REFERENCE.format(plant=0.0))
    c = json.loads((root / "bench" / "configs" / "smoke.w16.json")
                   .read_text())
    c.update(name="smoke.other", model_type="other",
             reference="bench/archs/other/reference.py",
             work="bench/archs/other/work.py",
             arch_fields={"capacity_factor": 2.0})
    c.update(config)
    c = {k: v for k, v in c.items() if v is not None}
    (root / "bench" / "configs" / "smoke.other.json").write_text(
        json.dumps(c))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(
        name="smoke.other", source="https://huggingface.co/ibm-granite",
        file="bench/configs/smoke.other.json", reduced=[], why="smoke"))
    spec["workloads"].append(dict(
        name="smoke.other.sessions", config="smoke.other",
        traffic="smoke_sessions", chips=1, why="smoke"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, before


def _main(root, **kwargs):
    return run.main(["--workload", "smoke.other.sessions", "--seed",
                     str(2 ** 31 + 41), "--seconds", "1.5", "--trace", "0"],
                    root=root, **kwargs)


@pytest.mark.parametrize("plant,correct", [(0.0, True), (1.0, False)])
def test_a_configuration_of_another_architecture_is_found_by_its_files_keys(
        smoke_root, tmp_path, monkeypatch, capsys, plant, correct):
    """The reference and the counts ``main`` uses are the ones the file
    names, its ``arch_fields`` reach the engine, and no file that was
    there is edited."""
    root, before = _other_checkout(smoke_root, tmp_path, plant)
    built = []
    model_config = run.model_config
    monkeypatch.setattr(run, "model_config",
                        lambda c: built.append(model_config(c)) or built[-1])
    res = _main(root, require=cpu_devices)
    err = capsys.readouterr().err
    assert res["correct"] is correct, res
    limit = res["check"]["max_gap"]["limit"]
    assert (res["check"]["max_gap"]["value"] > limit) is not correct
    assert "[check] reference bench/archs/other/reference.py over" in err
    assert "(bench/archs/other/work.py counts 123456789)" in err
    assert [cfg.capacity_factor for cfg in built] == [2.0]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    old = json.loads(before.pop(root / "BENCHMARK.json"))
    for key in ("configs", "workloads"):
        assert spec[key][:len(old[key])] == old[key]
    assert {k: v for k, v in spec.items()
            if k not in ("configs", "workloads")} == \
        {k: v for k, v in old.items() if k not in ("configs", "workloads")}
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("key,path,says", [
    ("reference", None, "names no 'reference' file"),
    ("reference", "bench/archs/other/missing.py", "is not a file"),
    ("reference", "../outside.py", "is not a file of the checkout"),
    ("reference", "bench/archs/other/empty.py", "has no gaps"),
    ("work", None, "names no 'work' file"),
    ("work", "bench/archs/other/empty.py",
     "has no weight_bytes, decode_step")])
def test_a_missing_or_incomplete_module_is_refused_before_the_build(
        smoke_root, tmp_path, key, path, says):
    root, _ = _other_checkout(smoke_root, tmp_path, **{key: path})

    def never(chips):
        raise AssertionError("the run went past its configuration")
    with pytest.raises(SystemExit, match=f"'smoke.other'.*{says}"):
        _main(root, require=never)
