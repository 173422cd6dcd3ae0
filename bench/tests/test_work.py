"""Operations and bytes from shapes, against the program's own sizes."""

import dataclasses
import json

import jax
import pytest

from bench import work

from bench.tests.conftest import SOURCE_CONFIG, config_file

# the served formats counted: the configuration as committed, and the same
# model at full depth as 4-bit codes over a kv8 pool
FORMATS = {"w16kv16": {},
           "w4kv8": dict(num_hidden_layers=40, serving=dict(w_bits=4,
                                                            kv_bits=8))}


def _config(fmt, **sizes):
    c = json.loads(config_file(SOURCE_CONFIG).read_text())
    change = dict(FORMATS[fmt])
    c["serving"].update(change.pop("serving", {}))
    c.update(change, **sizes)
    return c


def _program_bytes(c):
    from repro.configs import base as cb
    from repro.models import model
    cfg = dataclasses.replace(
        cb.get("granite_3_8b"), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        vocab=c["vocab_size"])
    tree = jax.eval_shape(lambda k: model.build_for_serving(
        k, cfg, c["serving"]["w_bits"]), jax.random.PRNGKey(0))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


SMOKE = dict(hidden_size=64, intermediate_size=160, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=511)


@pytest.mark.parametrize("name", sorted(FORMATS))
@pytest.mark.parametrize("sizes", [SMOKE, dict(num_hidden_layers=1)],
                         ids=["smoke", "published_widths_1_layer"])
def test_weight_bytes_equal_the_served_tree(name, sizes):
    c = _config(name, **sizes)
    assert work.weight_bytes(c) == _program_bytes(c)


def test_published_w4_tree_is_4_5_gb():
    # 40 layers are the 1-layer tree plus 39 more layers' worth
    c1 = _config("w4kv8", num_hidden_layers=1)
    c40 = _config("w4kv8")
    per_layer = sum(work.matrix_bytes(i, o, 4)
                    for i, o in work.layer_matmuls(c40)) + 8 * 4096
    assert work.weight_bytes(c40) == work.weight_bytes(c1) + 39 * per_layer
    assert work.weight_bytes(c40) == pytest.approx(4.502e9, rel=1e-3)


@pytest.mark.parametrize("name", sorted(FORMATS))
def test_kv_bytes_equal_the_pools(name):
    from repro.configs import base as cb
    from repro.models import kv_cache
    c = _config(name)
    cfg = dataclasses.replace(cb.get("granite_3_8b"),
                              n_layers=c["num_hidden_layers"])
    per = kv_cache.token_kv_bytes(cfg, c["serving"]["kv_bits"])
    assert work.token_kv_bytes(c) == per
    flops, nbytes = work.decode_step(c, [1000, 3000])
    assert nbytes == work.step_weight_bytes(c, 2) + 4002 * per


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_least_time_names_its_bound():
    pk = work.peaks("TPU v5 lite")
    assert work.least_seconds(1e12, 1e9, pk)[1] == "compute"
    assert work.least_seconds(1e9, 1e12, pk)[1] == "memory"
    c = _config("w4kv8")
    t, bound = work.least_seconds(*work.decode_step(c, [2000] * 16), pk)
    assert bound == "memory" and 5e-3 < t < 2e-2
