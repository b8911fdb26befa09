"""The comparison's sample and batch: served requests, finished or cut,
the longest always in, one fixed shape."""
import numpy as np

from bench import check


class Req:
    def __init__(self, rid, out, error=None):
        self.rid, self.output, self.error = rid, out, error


def test_sample_keeps_the_longest_and_drops_failed_and_empty():
    reqs = [Req(i, [1] * i) for i in range(30)]
    reqs.append(Req(99, [1] * 50, error="fetch failed"))
    got = check.sample(reqs, seed=2**40 + 1)
    assert len(got) == check.MAX_REQUESTS
    assert got[0].rid == 29
    assert all(r.output and r.error is None for r in got)
    assert [r.rid for r in check.sample(reqs, seed=2**40 + 1)] == \
        [r.rid for r in got]


def test_batch_targets_are_the_served_tokens():
    prompts = {1: np.array([5, 6, 7], np.int32), 2: np.array([8], np.int32)}
    reqs = [Req(1, [10, 11]), Req(2, [12, 13, 14])]
    tokens, targets = check.batch(reqs, prompts, max_len=8)
    assert tokens.shape == targets.shape == (check.MAX_REQUESTS, 8)
    # row 0: prompt 5 6 7, then served 10 (11 is never an input)
    assert tokens[0, :4].tolist() == [5, 6, 7, 10]
    assert targets[0].tolist() == [-1, -1, 10, 11, -1, -1, -1, -1]
    assert tokens[1, :3].tolist() == [8, 12, 13]
    assert targets[1].tolist() == [12, 13, 14, -1, -1, -1, -1, -1]
    assert (targets[2:] == -1).all()


def _tiny_ref(tied, **hooks):
    """Qwen2-MoE's tiny reference, its head tied (no ``lm_head/w``) or
    not, with the module hooks ``hooks``."""
    import types
    from bench.reference import qwen2_moe
    from conftest import _load

    def leaves(conf):
        glob, layers = qwen2_moe.leaves(conf)
        if tied:
            glob = {k: v for k, v in glob.items() if k != "lm_head/w"}
        return glob, layers

    return _load("tiny-qwen2-moe.json"), types.SimpleNamespace(
        leaves=leaves, layer=qwen2_moe.layer, **hooks)


def _tokens():
    rng = np.random.default_rng(2**41 + 5)
    tokens = rng.integers(0, 512, (2, 6)).astype(np.int32)
    targets = rng.integers(0, 512, (2, 6)).astype(np.int32)
    targets[1, :2] = -1
    return tokens, targets


def test_tied_head_gaps_equal_an_untied_head_of_the_embeddings_transpose(
        monkeypatch):
    """With no ``lm_head/w`` the reference's head is the embedding's
    transpose: the same gaps, the control's too, as an untied head drawn
    equal to that transpose."""
    from bench import weights
    from bench.reference import common
    seed = 2**34 + 9
    tokens, targets = _tokens()
    conf, tied = _tiny_ref(True)
    got = common.gaps(conf, seed, tokens, targets, tied, control=True)
    draw = weights.reference_globals

    def untied_as_the_transpose(seed, glob):
        g = draw(seed, glob)
        return dict(g, **{"lm_head/w": g["embed/tok"].T})

    monkeypatch.setattr(weights, "reference_globals", untied_as_the_transpose)
    conf, untied = _tiny_ref(False)
    want = common.gaps(conf, seed, tokens, targets, untied, control=True)
    assert want["gap"].max() > 0
    for k in ("gap", "control_gap"):
        np.testing.assert_array_equal(got[k], want[k])


def test_logits_divisor_scales_the_gaps():
    """A module's ``logits_divisor`` divides the reference's logits, so
    every gap by the same number; without one it is 1."""
    from bench.reference import common
    seed = 2**34 + 9
    tokens, targets = _tokens()
    conf, plain = _tiny_ref(True)
    conf, halved = _tiny_ref(True, logits_divisor=lambda conf: 2.0)
    a = common.gaps(conf, seed, tokens, targets, plain)["gap"]
    b = common.gaps(conf, seed, tokens, targets, halved)["gap"]
    assert a.max() > 0
    np.testing.assert_array_equal(b, a / 2)
