"""Batched request server: continuous batching over the compressed store
(DESIGN.md §4; the serving harness for the paper's real-workload runs, §5).

Two serving disciplines:

* **Continuous batching** (the default on the ZipMoE path): requests are
  admitted and retired *between decode steps*.  Every active request is a
  token stream at its own sequence position — prompt tokens are consumed
  one per step ("prefill-as-decode", which keeps every step the same
  single-token shape and lets the engine's prefetch overlap it), then
  sampled tokens until EOS / ``max_new_tokens``.  Per-request KV state
  lives in a shared fixed-size :class:`~repro.serving.kv_cache.KVPagePool`
  (allocate at admission, free at retirement — no whole-cache copies), and
  each step runs ONE ``ZipServer.decode_rows`` pass whose MoE layers
  submit a single Algorithm-1 block list over the union of all active
  requests' demand + predicted experts: the hierarchical cache, device
  slab, and live planner are shared multi-tenant resources.  Retirement
  backfills the freed slot from the queue at the next step boundary, and
  ``arrival_s`` offsets replay an arrival trace.
* **Epoch batching** (``continuous=False``, and the resident-params path):
  the legacy discipline — bucket same-length prompts, prefill together,
  decode in lockstep until every slot finishes, then refill.  Kept as the
  static-batch baseline the benchmarks compare against
  (``benchmarks/serving_real`` ``continuous_batching`` vs ``static_batch``).

API:
  Request      — one prompt + accounting (``ttft``, ``tpot_s``,
                 ``queue_delay_s``, ``output``, optional per-token
                 ``logits`` capture for the differential harness).
  BatchServer  — ``submit(prompt, max_new_tokens, arrival_s=..,
                 eos_token=..) -> rid``; ``run()`` serves the queue;
                 ``metrics()`` aggregates TTFT / TPOT / queue-delay
                 percentiles + throughput plus, on the ZipMoE path, the
                 engine's ``overlap_*`` / ``cache_*`` telemetry;
                 ``request_summary()`` is the per-request fairness/SLO
                 report (per-request cache hit rates included);
                 ``cache_summary()`` the full nested cache report.

``submit()`` clamps ``max_new_tokens`` against ``max_len - S`` so the KV
allocation can never silently overflow (see tests/test_overlap_serving.py);
the page pool's ``commit`` additionally hard-fails on any write past a
request's allocation.  Sampling is per-request keyed
(``fold_in(seed, rid)`` then per-token), so a request's trajectory is
independent of what shares its batch — with greedy decoding the emitted
logits are bit-identical to the same request served solo
(tests/test_continuous_batching.py).
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import StepFault
from repro.core.spans import span
from repro.serving.generate import make_steps, sample_tokens
from repro.serving.kv_cache import KVPagePool, grow_cache


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S]
    max_new_tokens: int = 16
    arrival_s: float = 0.0        # offset from run() start (trace replay)
    eos_token: Optional[int] = None
    record_logits: bool = False   # capture per-token logits (diff harness)
    submitted: float = field(default_factory=time.perf_counter)
    admitted: Optional[float] = None
    ttft: Optional[float] = None
    done: Optional[float] = None
    output: List[int] = field(default_factory=list)
    logits: List[np.ndarray] = field(default_factory=list)
    queue_delay_s: Optional[float] = None   # admission - eligibility
    error: Optional[str] = None   # set when retired by a StepFault

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time-per-output-token after the first token."""
        if self.ttft is None or self.done is None or len(self.output) < 2:
            return None
        return (self.done - (self.submitted + self.ttft)) / (len(self.output) - 1)


@dataclass
class _Slot:
    """One active request's decode-loop state (continuous batching)."""
    req: Request
    key: jax.Array                # per-request sampling key (fold_in rid)
    pos: int = 0                  # next token index to write
    next_tok: int = 0             # step input: prompt token or last sample


def _pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


class BatchServer:
    """Continuous batching (ZipMoE path) / epoch batching (resident path,
    or ``continuous=False`` as the static-batch baseline)."""

    def __init__(self, params, cfg, *, max_batch: int = 8, max_len: int = 256,
                 temperature: float = 0.0, zip_server=None,
                 max_concurrency: Optional[int] = None,
                 continuous: bool = True, page_size: int = 16,
                 n_pages: Optional[int] = None, seed: int = 0):
        self.params, self.cfg = params, cfg
        self.max_batch, self.max_len = max_batch, max_len
        self.max_concurrency = max_concurrency or max_batch
        self.temperature = temperature
        self.zip = zip_server
        self.continuous = continuous and zip_server is not None
        self.page_size = page_size
        self.n_pages = n_pages
        self._base_key = jax.random.PRNGKey(seed)
        if zip_server is None:
            self.pf, self.dec = make_steps(cfg)
        self.queue: "collections.deque[Request]" = collections.deque()
        self.finished: List[Request] = []
        self._rid = 0
        # test/telemetry hook: called right after a request retires (its
        # pages freed, stats final) — the interleaving fuzz test asserts
        # cache invariants here, between steps
        self.on_retire: Optional[Callable[[Request], None]] = None

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16, *,
               arrival_s: float = 0.0, eos_token: Optional[int] = None,
               record_logits: bool = False) -> int:
        """Enqueue a request.  Prompts that leave no room for even one new
        token under ``max_len`` are rejected; oversized ``max_new_tokens``
        are clamped so S + new never overflows the KV allocation.
        ``arrival_s`` delays admission to that offset from ``run()`` start
        (arrival-trace replay; 0 = immediately eligible)."""
        prompt = np.asarray(prompt, np.int32)
        S = len(prompt)
        if S < 1 or S + 1 > self.max_len:
            raise ValueError(
                f"prompt length {S} must be in [1, max_len={self.max_len})")
        max_new_tokens = max(1, min(max_new_tokens, self.max_len - S))
        self._rid += 1
        self.queue.append(Request(self._rid, prompt, max_new_tokens,
                                  arrival_s=float(arrival_s),
                                  eos_token=eos_token,
                                  record_logits=record_logits))
        return self._rid

    def run(self) -> List[Request]:
        if self.continuous:
            return self._run_continuous()
        while self.queue:
            batch = self._take_batch()
            self._serve_batch(batch)
        return self.finished

    # -- continuous batching (ZipMoE path) -------------------------------
    def _make_pool(self) -> KVPagePool:
        cc = self.max_concurrency
        pages_per = -(-self.max_len // self.page_size)
        # default: every slot can hold a max_len request, so admission
        # never stalls on pages; an explicit smaller n_pages makes pages
        # the admission constraint instead (all-or-nothing at admission —
        # active requests hold their full budget, so no deadlock)
        n_pages = self.n_pages or cc * pages_per
        return KVPagePool(self.cfg, page_size=self.page_size,
                          n_pages=n_pages, max_slots=cc)

    def _admit(self, active: List[_Slot], pool: KVPagePool, t0: float):
        """Admit queued requests into free slots at a step boundary.
        Strict FIFO; a head whose ``arrival_s`` is still in the future
        blocks admission (and is slept for when nothing is active)."""
        while self.queue and len(active) < self.max_concurrency:
            nxt = self.queue[0]
            wait = (t0 + nxt.arrival_s) - time.perf_counter()
            if wait > 0:
                if active:
                    break
                time.sleep(wait)
            r = self.queue[0]
            try:
                pool.alloc(r.rid, len(r.prompt) + r.max_new_tokens)
            except RuntimeError:
                if not active:         # cannot ever fit: configuration error
                    raise
                break                  # wait for a retirement to free pages
            self.queue.popleft()
            now = time.perf_counter()
            r.admitted = now
            r.queue_delay_s = now - max(r.submitted, t0 + r.arrival_s)
            active.append(_Slot(r, key=jax.random.fold_in(self._base_key,
                                                          r.rid),
                                next_tok=int(r.prompt[0])))

    def _run_continuous(self) -> List[Request]:
        pool = self.pool = self._make_pool()
        active: List[_Slot] = []
        t0 = time.perf_counter()
        while self.queue or active:
            with span("zipmoe.batch.admit"):
                self._admit(active, pool, t0)
            rids = [s.req.rid for s in active]
            tokens = jnp.asarray([[s.next_tok] for s in active], jnp.int32)
            positions = np.asarray([s.pos for s in active], np.int32)
            with span("zipmoe.kv.gather"):
                views = pool.gather(rids)  # gen-checked: KV pages, not slabs
            try:
                lg, views = self.zip.decode_rows(tokens, views, positions,
                                                 owners=rids)
            except StepFault as f:
                # per-request failure isolation: retire ONLY the rows whose
                # experts could not be fetched, then re-run the step with
                # the survivors.  Nothing was committed (the fault fires
                # before any KV write) and sampling is per-request keyed,
                # so survivor trajectories are unchanged — bit-identical to
                # a fault-free run (tests/test_faults.py).
                bad = {active[b].req.rid for b in f.rows if b < len(active)}
                if not bad:          # defensive: always retire someone, or
                    bad = set(rids)  # a persistent fault would spin forever
                now = time.perf_counter()
                for s in [s for s in active if s.req.rid in bad]:
                    r = s.req
                    r.error = str(f)
                    r.done = now
                    pool.free(r.rid)
                    active.remove(s)
                    self.finished.append(r)
                    if self.on_retire is not None:
                        self.on_retire(r)
                continue
            with span("zipmoe.kv.commit"):
                pool.commit(views, rids, positions)
            retired: List[_Slot] = []
            with span("zipmoe.batch.sample"):
                for b, s in enumerate(active):
                    r = s.req
                    s.pos += 1
                    if s.pos < len(r.prompt):      # prefill-as-decode
                        s.next_tok = int(r.prompt[s.pos])
                        continue
                    row = lg[b, -1]
                    step_key = jax.random.fold_in(s.key, len(r.output))
                    tok = int(sample_tokens(row[None], step_key,
                                            self.temperature)[0])
                    now = time.perf_counter()
                    if r.ttft is None:
                        r.ttft = now - r.submitted
                    r.output.append(tok)
                    if r.record_logits:
                        r.logits.append(np.asarray(row, np.float32))
                    s.next_tok = tok
                    if (len(r.output) >= r.max_new_tokens
                            or (r.eos_token is not None
                                and tok == r.eos_token)):
                        r.done = now
                        retired.append(s)
            for s in retired:                      # free pages, backfill next
                pool.free(s.req.rid)
                active.remove(s)
                self.finished.append(s.req)
                if self.on_retire is not None:
                    self.on_retire(s.req)
            if not active:
                # nothing left to hide the speculative tails under: finish
                # the in-flight prediction jobs so cache byte accounting is
                # stable (and nothing leaks across an idle gap / shutdown)
                self.zip.drain_pending()
        return self.finished

    # -- epoch batching (resident path / static-batch baseline) ----------
    def _take_batch(self) -> List[Request]:
        if not self.queue:
            return []
        # bucket by prompt length for a single prefill shape
        first_len = len(self.queue[0].prompt)
        batch = []
        rest = collections.deque()
        while self.queue and len(batch) < self.max_batch:
            r = self.queue.popleft()
            if len(r.prompt) == first_len:
                batch.append(r)
            else:
                rest.append(r)
        self.queue.extendleft(reversed(rest))
        return batch

    def _prefill(self, prompts: np.ndarray, max_new: int):
        """Returns (last-position logits [B, V], decode cache, decode fn)."""
        B, S = prompts.shape
        if self.zip is not None:
            # compressed-store path: the prompt streams through the ZipMoE
            # decode step (engine prefetch overlaps reconstruction with it)
            cache = self.zip.init_cache(B, S + max_new)
            logits = None
            for i in range(S):
                logits, cache = self.zip.decode_step(
                    jnp.asarray(prompts[:, i:i + 1]), cache, i)

            def dec(tok, cache, pos):
                return self.zip.decode_step(tok, cache, pos)
        else:
            logits, cache = self.pf(self.params, {"tokens": jnp.asarray(prompts)})
            cache = grow_cache(self.cfg, cache, B, S + max_new)

            def dec(tok, cache, pos):
                return self.dec(self.params, {"tokens": tok}, cache,
                                jnp.int32(pos))
        return logits[:, -1], cache, dec

    def _serve_batch(self, batch: List[Request]):
        S = len(batch[0].prompt)
        prompts = np.stack([r.prompt for r in batch])
        max_new = max(r.max_new_tokens for r in batch)
        key = jax.random.PRNGKey(0)
        logits, cache, dec = self._prefill(prompts, max_new)
        tok = sample_tokens(logits, key, self.temperature)
        tok.block_until_ready()
        now = time.perf_counter()
        alive = set()
        for b, r in enumerate(batch):
            r.ttft = now - r.submitted
            r.output.append(int(tok[b]))
            if r.record_logits:
                r.logits.append(np.asarray(logits[b], np.float32))
            if len(r.output) >= r.max_new_tokens:
                r.done = now
            else:
                alive.add(b)
        for i in range(max_new - 1):
            if not alive:
                break
            key, sub = jax.random.split(key)
            lg, cache = dec(tok[:, None], cache, S + i)
            tok = sample_tokens(lg[:, -1], sub, self.temperature)
            now = time.perf_counter()
            for b in list(alive):
                r = batch[b]
                r.output.append(int(tok[b]))
                if r.record_logits:
                    r.logits.append(np.asarray(lg[b, -1], np.float32))
                if len(r.output) >= r.max_new_tokens:
                    r.done = now
                    alive.discard(b)
        now = time.perf_counter()
        for r in batch:
            if r.done is None:
                r.done = now
        self.finished.extend(batch)

    # -- metrics ---------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        if not self.finished:
            return {}
        ttfts = [r.ttft for r in self.finished if r.ttft is not None]
        tpots = [r.tpot_s for r in self.finished if r.tpot_s is not None]
        qdels = [r.queue_delay_s for r in self.finished
                 if r.queue_delay_s is not None]
        total_toks = sum(len(r.output) for r in self.finished)
        span = (max(r.done for r in self.finished) -
                min(r.submitted for r in self.finished))
        m = {"n_requests": len(self.finished),
             "n_failed": sum(1 for r in self.finished if r.error),
             "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
             "ttft_p50_s": _pct(ttfts, 50) if ttfts else 0.0,
             "ttft_p95_s": _pct(ttfts, 95) if ttfts else 0.0,
             "throughput_tok_s": total_toks / max(span, 1e-9)}
        if tpots:
            m["mean_tpot_s"] = float(np.mean(tpots))
            m["tpot_p50_s"] = _pct(tpots, 50)
            m["tpot_p95_s"] = _pct(tpots, 95)
        if qdels:
            m["queue_delay_p50_s"] = _pct(qdels, 50)
            m["queue_delay_p95_s"] = _pct(qdels, 95)
        if self.zip is not None:
            m.update({f"overlap_{k}": v
                      for k, v in self.zip.overlap_summary().items()})
            cs = self.zip.cache_summary()
            m.update({"cache_mode": cs["mode"],
                      "cache_hit_rate": cs["hit_rate"],
                      "cache_accesses": cs["accesses"],
                      "cache_misses": cs["misses"],
                      "cache_evictions": cs["evictions"]})
        return m

    def request_summary(self) -> Dict[int, Dict[str, object]]:
        """Per-request fairness/SLO accounting: latency (TTFT / TPOT /
        queue delay) joined with the ZipServer's per-request cache stats
        (accesses, hits-at-step-start, hit rate) — the multi-tenant
        complement to the shared-pool :meth:`cache_summary`."""
        per_cache = {}
        if self.zip is not None and hasattr(self.zip, "request_summary"):
            per_cache = self.zip.request_summary()
        out: Dict[int, Dict[str, object]] = {}
        for r in self.finished:
            d: Dict[str, object] = {
                "ttft_s": r.ttft, "tpot_s": r.tpot_s,
                "queue_delay_s": r.queue_delay_s,
                "n_tokens": len(r.output), "error": r.error}
            d.update({f"cache_{k}": v
                      for k, v in per_cache.get(r.rid, {}).items()})
            out[r.rid] = d
        return out

    def cache_summary(self, per_layer: bool = False):
        """Full §3.4 cache telemetry of the underlying ZipServer (per-pool
        hit counts, residency transitions); ``{}`` on the resident path."""
        if self.zip is None:
            return {}
        return self.zip.cache_summary(per_layer=per_layer)
