"""The program's ArchConfig as a configuration file states it: the ten keys
of ``run.model_config``'s table, then the file's optional ``arch_fields``."""

import dataclasses
import json

import pytest

from bench import run
from bench.tests.conftest import SOURCE_CONFIG, config_file


def _granite(**changes):
    c = json.loads(config_file(SOURCE_CONFIG).read_text())
    c.update(changes)
    return c


def test_the_granite_file_builds_the_arch_config_it_built_before():
    """Before ``arch_fields`` the harness set the table's ten fields on the
    registered config; the Granite file has no ``arch_fields`` and builds
    that config, field by field."""
    from repro.configs import base as cb
    c = _granite()
    assert "arch_fields" not in c
    before = dataclasses.replace(
        cb.get("granite_3_8b"), n_layers=20, d_model=4096, d_ff=12800,
        n_heads=32, n_kv_heads=8, head_dim=128, vocab=49155,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False)
    got = run.model_config(c)
    assert dataclasses.asdict(got) == dataclasses.asdict(before)


@pytest.mark.parametrize("fields,want", [
    ({"family": "moe", "n_experts": 72, "top_k": 10},
     {"family": "moe", "n_experts": 72, "top_k": 10}),
    # no field of ArchConfig holds a list yet (layer kinds will)
    ({"sliding_window": [[1, 2], 3]}, {"sliding_window": ((1, 2), 3)})],
    ids=["applied", "a_list_is_a_tuple"])
def test_arch_fields_are_applied(fields, want):
    got = run.model_config(_granite(arch_fields=fields))
    assert {k: getattr(got, k) for k in want} == want
    hash(got)
    plain = run.model_config(_granite())
    assert dataclasses.replace(got, **{k: getattr(plain, k)
                                       for k in fields}) == plain


@pytest.mark.parametrize("fields,says", [
    ({"n_mamba_layers": 36}, "'n_mamba_layers', which ArchConfig has no"),
    ({"n_layers": 40}, "'n_layers', which the file holds as "
                       "'num_hidden_layers'")],
    ids=["unknown_field", "a_field_of_the_table"])
def test_arch_fields_are_refused(fields, says):
    with pytest.raises(SystemExit, match=says):
        run.model_config(_granite(arch_fields=fields))
