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


@pytest.mark.parametrize("name", ["tiny-qwen2-moe.json", "tiny-deepseek-v2.json"])
def test_weights_equal_the_parents_leaf_for_leaf(name):
    """Every leaf of the program's tree, at its path, has the bytes, type
    and shape that the layout had before it followed the program's period
    (pinned in ``parent-pins.json``)."""
    import hashlib
    conf = _load(name)
    with tiny_widths(conf):
        cfg = harness.program_config(conf)
    glob, layers = spec.reference_module(conf).leaves(conf)
    p = weights.program_params(2**35 + 1, glob, layers, cfg.first_dense)
    got = {jax.tree_util.keystr(k):
           hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           + f" {v.dtype} {list(v.shape)}"
           for k, v in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert got == _load("parent-pins.json")["weights_sha256_16"][name]


HYBRID_SEED = 2**35 + 3


@pytest.fixture(scope="module")
def hybrid():
    """The tiny hybrid's configuration, its program config, its tensors
    and the program's tree of them."""
    import tiny_hybrid
    from repro.models.transformer import stack_layout
    conf = _load("tiny-jamba.json")
    with tiny_widths(conf):
        cfg = harness.program_config(conf, tiny_hybrid)
    glob, layers = tiny_hybrid.leaves(conf)
    prefix, period, _ = stack_layout(cfg)
    p = weights.program_params(HYBRID_SEED, glob, layers, len(prefix), period)
    return conf, cfg, (glob, layers), p


def test_hybrid_layout_is_the_programs(hybrid):
    """A period of 8 layer kinds (Mamba or attention, MLP or experts) in
    two blocks, with a tied head: the tree's shapes and types are
    ``init_params``'."""
    from repro.models import init_params
    from repro.models.transformer import stack_layout
    conf, cfg, _, p = hybrid
    prefix, period, blocks = stack_layout(cfg)
    assert (len(prefix), period, blocks) == (0, 8, 2)
    want = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), p)
    assert got == jax.tree.map(lambda x: (x.shape, x.dtype), want)
    assert "lm_head" not in p


@pytest.mark.parametrize("layer,mixer,tensor", [(9, "mamba", "w_x"),
                                                (11, "attn", "wk")])
def test_hybrid_unstack_puts_each_layer_at_its_index(hybrid, layer, mixer,
                                                     tensor):
    """``unstack_layers``, which the server uses, finds layer ``l``'s
    drawn tensors at index ``l``: block 1 of ``sub_1`` (Mamba, experts)
    and of ``sub_3`` (attention, experts)."""
    from repro.serving.kv_cache import unstack_layers
    conf, cfg, (glob, layers), p = hybrid
    per = unstack_layers(p["decoder"], cfg)
    ref = weights.reference_layer(HYBRID_SEED, layer, layers[layer])
    for name in (f"{mixer}/{tensor}", "ffn/w_down"):
        a, b = name.split("/")
        np.testing.assert_array_equal(np.asarray(per[layer][a][b], np.float32),
                                      np.asarray(ref[name]))
    assert not np.array_equal(np.asarray(per[layer - 8]["ffn"]["w_down"],
                                         np.float32),
                              np.asarray(ref["ffn/w_down"]))


def test_a_layer_unlike_its_sub_raises_and_names_it(hybrid):
    conf, cfg, (glob, layers), _ = hybrid
    layers = [dict(s) for s in layers]
    shape, dtype, init = layers[13]["ffn/w_up"]
    layers[13]["ffn/w_up"] = (shape[:-1] + (shape[-1] + 1,), dtype, init)
    with pytest.raises(ValueError, match=r"layer 13 differs from layer 5.*"
                                         r"sub_5.*ffn/w_up"):
        weights.program_params(1, glob, layers, 0, 8)
    with pytest.raises(ValueError, match="do not fill"):
        weights.program_params(1, glob, layers[:12], 0, 8)
