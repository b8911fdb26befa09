"""The cost functions against hand counts at the served expert shape,
2048 x 1408."""
import json
import os

import pytest

from bench.costs import least_seconds, model
from bench.costs import slab_ragged_gemm as ragged
from bench.costs import recover_bf16 as recover
from bench.costs import slab_splice_admit as splice
from conftest import ROOT

D, F = 2048, 1408


def test_ragged_gemm_counts():
    # 32 routed pairs over 25 distinct experts
    assert ragged.flops(32, D, F) == 2 * 32 * 2048 * 1408 * 3 == 553_648_128
    weights = 25 * 3 * 2048 * 1408 * 2                  # 432,537,600
    reads = 32 * (2048 + 2048 + 1408) * 2               # 352,256
    writes = 32 * (1408 * 2 + 1408 * 2 + 2048 * 4)      # 442,368
    assert ragged.nbytes(25, 32, D, F) == weights + reads + writes \
        == 433_332_224


def test_splice_admit_counts():
    assert splice.nbytes(3, D * F) == 3 * 2_883_584 * 4 == 34_603_008


def test_recover_counts():
    # two u8 planes in, one bf16 tensor out, per element
    assert recover.nbytes(5, D * F) == 5 * 2048 * 1408 * (1 + 1 + 2) \
        == 57_671_680


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert least_seconds(553_648_128, 433_332_224, peaks) == \
        pytest.approx(433_332_224 / 819e9)
    assert least_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)


def test_model_flops_per_token_qwen():
    with open(os.path.join(ROOT, "bench/configs/qwen1.5-moe-a2.7b.l4.json")) as f:
        conf = json.load(f)
    attn = 4 * 2048 * 2048                     # q, k, v, o
    router = 2048 * 60
    experts = 4 * 3 * 2048 * 1408              # top-4 of 60
    shared = 3 * 2048 * 5632
    head = 2048 * 151936
    weights = 4 * (attn + router + experts + shared) + head
    assert weights == 655_589_376
    assert model.flops_per_token(conf, 0) == 2 * weights
    # attention over 100 positions: scores and values, 16 heads of 128
    assert model.flops_per_token(conf, 100) - model.flops_per_token(conf, 0) \
        == pytest.approx(2 * 4 * 16 * 100 * (128 + 128))


def test_model_flops_per_token_deepseek():
    with open(os.path.join(ROOT, "bench/configs/deepseek-v2-lite.l5.json")) as f:
        conf = json.load(f)
    attn = (2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256
            + 16 * 128 * 2048)
    dense = 3 * 2048 * 10944
    moe = 2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    head = 2048 * 102400
    assert model.flops_per_token(conf, 0) == \
        2 * (5 * attn + dense + 4 * moe + head)


@pytest.mark.parametrize("name", ["tiny-qwen2-moe.json", "tiny-deepseek-v2.json",
                                  "qwen1.5-moe-a2.7b.l4", "deepseek-v2-lite.l5"])
def test_flops_per_token_equals_the_parents(name):
    """The same float at ``ctx`` 64 as before attention was charged by
    layer (``parent-pins.json``), so ``step_mfu`` does not move."""
    from conftest import _load
    if name.endswith(".json"):
        conf = _load(name)
    else:
        with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
            conf = json.load(f)
    assert model.flops_per_token(conf, 64) == \
        _load("parent-pins.json")["flops_per_token_ctx64"][name]


def test_hybrid_flops_charge_context_to_attention_layers_only():
    """The tiny hybrid (16 layers: 2 attention, 14 Mamba; experts top-2
    of 4 on the 8 odd layers, an MLP on the rest; a tied head): context
    costs in the 2 attention layers alone, the tied head counts once as
    the head, and each Mamba layer adds its module's state FLOPs."""
    import tiny_hybrid
    from conftest import _load
    conf = _load("tiny-jamba.json")
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64
    mamba = 2 * 64 * 128 + 3 * 64 * 8 + 4 * 144 + 128 * 64
    mlp = 3 * 64 * 96
    moe = 64 * 4 + 2 * 3 * 64 * 96
    head = 64 * 256
    state = 2 * 3 * 128 * 8
    assert tiny_hybrid.state_flops(conf) == state
    base = model.flops_per_token(conf, 0, tiny_hybrid)
    assert base == 2 * (2 * attn + 14 * mamba + 8 * mlp + 8 * moe + head) \
        + 14 * state
    # 2 layers attend over 64 positions: 4 heads of 16, scores and values
    assert model.flops_per_token(conf, 64, tiny_hybrid) - base == \
        2 * 2 * 4 * 64 * (16 + 16)
