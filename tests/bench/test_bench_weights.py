"""Seeded weights: the reference draws each layer again, bit for bit."""
import json

import jax
import numpy as np
import pytest

from bench import reference as R
from bench import weights as W
from bench.common import program_config
from bench_tiny import CONFIG


@pytest.fixture(scope="module")
def tiny():
    from repro.models import build

    cfg = program_config(CONFIG)
    spec = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    return cfg, spec


def test_layer_leaf_matches_the_served_tree(tiny):
    cfg, spec = tiny
    seed = 2**31 + 77
    params = W.make_params(spec, seed)
    flat = {W._path_str(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for path, s, stacked in W.leaf_specs(spec):
        for layer in range(s.shape[0] if stacked else 1):
            want = flat[path][layer] if stacked else flat[path]
            got = W.layer_leaf(seed, path, layer, want.shape, s.dtype)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_seeds_differ(tiny):
    _, spec = tiny
    a = W.make_params(spec, 1)["blocks"]["attn"]["wq"]
    b = W.make_params(spec, 2)["blocks"]["attn"]["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_layout_matches_program_and_rejects_a_change(tiny):
    _, spec = tiny
    R.check_layout(CONFIG, spec)
    with pytest.raises(ValueError):
        R.check_layout(dict(CONFIG, attention_bias=False), spec)


def test_program_config_refuses_a_width_the_file_does_not_state():
    with pytest.raises(ValueError):
        program_config(dict(CONFIG, intermediate_size=256))


@pytest.mark.parametrize("name", ["qwen2-7b-14l", "deepseek-moe-16b-7l"])
def test_published_configs_match_the_program(name):
    from pathlib import Path

    from repro.models import build

    c = json.loads((Path(__file__).resolve().parents[2] / "bench" / "configs"
                    / f"{name}.json").read_text())
    cfg = program_config(c)
    R.check_layout(c, jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0)))
