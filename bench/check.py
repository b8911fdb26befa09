"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the timed path served -- finished, or cut at the window's end
with the tokens they had by then; drawn from the seed, the one with the
most served tokens always among them -- is run through the configuration's
plain float32 reference: each prompt with its served tokens, in one batch
of fixed shape (so it compiles once), one layer at a time.  Decoding is
greedy, so every served token should be the reference's best up to
rounding.  The number compared is the widest gap
by which a served token's reference logit lies below the reference's best
logit at that position (``logit_gap_max``), against the cell's limit.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

MAX_REQUESTS = 16


def sample(served: List, seed: int) -> List:
    """Up to ``MAX_REQUESTS`` requests with served tokens and no error,
    drawn from ``seed``, the one with the most served tokens first."""
    ok = [r for r in served if not r.error and r.output]
    if not ok:
        return []
    ok.sort(key=lambda r: (-len(r.output), r.rid))
    rest = ok[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    return [ok[0]] + [rest[i] for i in order[:MAX_REQUESTS - 1]]


def batch(reqs: List, prompts: Dict[int, np.ndarray], max_len: int):
    """tokens [MAX_REQUESTS, max_len]: prompt then served tokens;
    targets: the served token each position's logits chose, -1 elsewhere
    and in the padding rows."""
    seqs = [np.concatenate([prompts[r.rid], np.asarray(r.output[:-1],
                                                        np.int32)])
            for r in reqs]
    tokens = np.zeros((MAX_REQUESTS, max_len), np.int32)
    targets = np.full((MAX_REQUESTS, max_len), -1, np.int32)
    for i, (r, s) in enumerate(zip(reqs, seqs)):
        tokens[i, :len(s)] = s
        p = len(prompts[r.rid])
        targets[i, p - 1:p - 1 + len(r.output)] = r.output
    return tokens, targets


def readings(ref, conf: dict, seed: int, served: List,
             prompts: Dict[int, np.ndarray], max_len: int,
             control: bool = False) -> dict:
    reqs = sample(served, seed)
    if not reqs:
        return {"requests": 0, "tokens": 0, "logit_gap_max": None,
                "control_gap_max": None}
    tokens, targets = batch(reqs, prompts, max_len)
    out = ref.gaps(conf, seed, tokens, targets, control=control)
    r = {"requests": len(reqs), "tokens": int((targets >= 0).sum()),
         "logit_gap_max": float(out["gap"].max())}
    if control:
        r["control_gap_max"] = float(out["control_gap"].max())
    return r


def compare(ref, conf: dict, seed: int, served: List,
            prompts: Dict[int, np.ndarray], limits: dict, max_len: int,
            control: bool = False) -> dict:
    """``correct`` and the numbers compared with their limits.

    With ``control``, the float8 control's first-choice token at each
    position of the same prompts and served tokens takes the served token's
    place and is judged by the same limit, so a comparison that can tell
    the two apart reports ``correct`` false (for setting and proving the
    limit; the benchmark's runs do not use it)."""
    r = readings(ref, conf, seed, served, prompts, max_len, control)
    limit = limits.get("logit_gap_max")
    value = r["control_gap_max"] if control else r["logit_gap_max"]
    ok = value is not None and limit is not None and value <= limit
    return {"correct": ok, "compared_requests": r["requests"],
            "compared_tokens": r["tokens"],
            "served_gap_max": r["logit_gap_max"],
            "numbers": {"logit_gap_max": {"value": value, "limit": limit}}}
