"""The one traffic generator: a traffic file's parameters -> requests.

A closed loop of ``clients`` clients: each sends its next request when its
last one retires.  Requests come from one stream in the order they are
sent.  Lengths do not depend on the seed, so every seed does the same work:
the stream repeats blocks of ``strata`` requests, request ``j`` of a block
taking prompt quantile ``q(j)`` and output quantile ``q(j * step mod
strata)`` of the two length distributions, where ``q(i) = (i + 1/2) /
strata`` in bit-reversed order of ``i`` (so any run of requests spans the
distributions) and ``step`` is coprime to ``strata`` (so long prompts do
not all get long outputs).  The seed draws the prompt tokens: Zipf(``s``)
over a seeded permutation of the vocabulary.

Each client's first request belongs to the ramp: a ``ramp.prompt_len``
prompt and an output of ``ramp.output_len_max * (c + 1) / clients``
tokens for client ``c``, so the clients retire at staggered steps and the
window opens on desynchronised requests.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    prompt: np.ndarray      # int32 [S]
    max_new: int


def _quantiles(spec: dict, n: int) -> List[int]:
    """The ``n`` mid-quantiles of a length distribution, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(spec["max"], max(spec["min"], round(v)))))
    return out


def _bit_reversed(n: int) -> List[int]:
    """0..n-1 in bit-reversed order (van der Corput), for any n."""
    bits = max(1, (n - 1).bit_length())
    rev = lambda i: int(format(i, f"0{bits}b")[::-1], 2)
    return sorted(range(n), key=rev)


KEYS = {"kind", "clients", "max_len", "prompt_len", "output_len", "tokens",
        "strata", "ramp"}


class Stream:
    """Requests of one traffic file, drawn from ``seed``.  Clients send
    with no think time and decode greedily; a file that asks for anything
    the generator does not implement is refused."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        if set(traffic) != KEYS:
            raise ValueError(f"traffic keys {sorted(set(traffic) ^ KEYS)} "
                             f"are not the generator's {sorted(KEYS)}")
        if traffic["kind"] != "closed_loop":
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
        self.t = traffic
        self.clients = int(traffic["clients"])
        self.rng = np.random.default_rng(seed)
        tok = traffic["tokens"]
        if tok["dist"] != "zipf":
            raise ValueError(f"unknown token distribution {tok['dist']!r}")
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        p = ranks ** -float(tok["s"])
        self._cdf = np.cumsum(p / p.sum())
        self._perm = self.rng.permutation(vocab_size).astype(np.int32)
        n = int(traffic["strata"])
        pq = _quantiles(traffic["prompt_len"], n)
        oq = _quantiles(traffic["output_len"], n)
        order = _bit_reversed(n)
        step = next(k for k in range(n // 2 + 1, n) if math.gcd(k, n) == 1)
        self.block = [(pq[order[j]], oq[order[j * step % n]])
                      for j in range(n)]
        self._sent = 0

    def tokens(self, n: int) -> np.ndarray:
        u = self.rng.random(n)
        ranks = np.minimum(np.searchsorted(self._cdf, u), len(self._cdf) - 1)
        return self._perm[ranks]

    def ramp(self) -> List[Request]:
        """Each client's first request, client ``c`` at index ``c``."""
        r = self.t["ramp"]
        return [Request(self.tokens(int(r["prompt_len"])),
                        max(1, round(r["output_len_max"] * (c + 1)
                                     / self.clients)))
                for c in range(self.clients)]

    def max_alloc(self) -> int:
        """The most tokens (prompt and output) one request can hold."""
        ramp = self.t["ramp"]
        return min(int(self.t["max_len"]),
                   max(max(s + n for s, n in self.block),
                       int(ramp["prompt_len"]) + int(ramp["output_len_max"])))

    def next(self) -> Request:
        s, n = self.block[self._sent % len(self.block)]
        self._sent += 1
        return Request(self.tokens(s), min(n, int(self.t["max_len"]) - s))
