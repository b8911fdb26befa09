"""Seeded weights: the program's layout, and the reference's draw of the
same numbers one layer at a time."""
import jax
import numpy as np
import pytest

from bench import harness, spec, weights
from conftest import _load, tiny_widths


@pytest.mark.parametrize("name", ["tiny-qwen2-moe.json", "tiny-deepseek-v2.json"])
def test_program_layout_and_reference_draw(name):
    from repro.models import init_params
    conf = _load(name)
    with tiny_widths(conf):
        cfg = harness.program_config(conf)
    glob, layers = spec.reference_module(conf).leaves(conf)
    p = weights.program_params(2**35 + 1, glob, layers, cfg.first_dense)
    want = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), p)
    assert got == jax.tree.map(lambda x: (x.shape, x.dtype), want)
    # the reference draws layer l's tensors equal to the program's
    last = len(layers) - 1
    ref = weights.reference_layer(2**35 + 1, last, layers[last])
    stack = p["decoder"]["stack"]["sub_0"]
    np.testing.assert_array_equal(
        np.asarray(stack["ffn"]["w_up"][-1], np.float32),
        np.asarray(ref["ffn/w_up"]))
    np.testing.assert_array_equal(
        np.asarray(stack["attn"]["wo"][-1], np.float32),
        np.asarray(ref["attn/wo"]))


def test_seeds_differ_and_repeat():
    conf = _load("tiny-qwen2-moe.json")
    glob, layers = spec.reference_module(conf).leaves(conf)
    a = weights.reference_layer(7, 0, layers[0])["ffn/w_gate"]
    b = weights.reference_layer(7, 0, layers[0])["ffn/w_gate"]
    c = weights.reference_layer(2**32 + 7, 0, layers[0])["ffn/w_gate"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
