"""The window's end-to-end accounting, from timestamps alone.

A step record says when the step's logits were ready and which request
each row served at which position; a row whose position is at or past its
prompt's last token emitted a token then.  From those:

* ``tokens_per_s``: tokens emitted in ``[start, end]`` over ``end - start``;
* ``itl``: every gap between consecutive tokens of one request that ends
  in the window.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import quantiles
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class Step:
    t_call: float
    t_ready: float
    owners: Sequence[int]
    positions: Sequence[int]


def emissions(steps: List[Step], prompt_len: Dict[int, int]):
    """rid -> token emission times, in order."""
    out: Dict[int, List[float]] = {}
    for s in steps:
        for rid, pos in zip(s.owners, s.positions):
            if pos >= prompt_len[rid] - 1:
                out.setdefault(rid, []).append(s.t_ready)
    return out


def pct(xs, q: float) -> float:
    """The ``q``-th percentile (linear interpolation)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


def account(steps: List[Step], prompt_len: Dict[int, int], start: float,
            end: float) -> dict:
    em = emissions(steps, prompt_len)
    n_tok = sum(start <= t <= end for ts in em.values() for t in ts)
    gaps = [b - a for ts in em.values() for a, b in zip(ts, ts[1:])
            if start <= b <= end]
    return {"tokens": n_tok, "tokens_per_s": n_tok / (end - start),
            "itl_s": gaps}


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as ``statistics.quantiles``
    gives the quartiles."""
    q1, q2, q3 = quantiles(values, n=4)
    return (q3 - q1) / q2
