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
