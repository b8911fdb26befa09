"""The comparison catches a broken timed path: the tiny cell's run with a
fault planted under the served path comes out not correct."""
import jax.numpy as jnp

from conftest import run_tiny


def _emitting(owners, positions, prompt_len):
    return [b for b, (rid, p) in enumerate(zip(owners, positions))
            if p >= prompt_len[rid] - 1]


def alter_token(lg, owners, positions, prompt_len):
    """Every token the first row emits is replaced where it is produced:
    its logits favour the next vocabulary id instead."""
    rows = _emitting(owners, positions, prompt_len)
    if not rows:
        return lg
    b = rows[0]
    tok = int(jnp.argmax(lg[b, -1]))
    return lg.at[b, -1, (tok + 1) % lg.shape[-1]].set(1e4)


def drop_half(lg, owners, positions, prompt_len):
    """The second half of the batch is left out: its rows get no logits."""
    B = lg.shape[0]
    return lg.at[B // 2:].set(0)


def test_an_altered_token_is_not_correct():
    out = run_tiny(tamper=alter_token, seed=11)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_half_the_batch_left_out_is_not_correct():
    out = run_tiny(tamper=drop_half, seed=12)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_unbroken_path_on_the_same_seeds_is_correct():
    assert run_tiny(seed=11)["correct"] is True
