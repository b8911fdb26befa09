"""ZipMoE-integrated serving: decode with engine-fed expert weights.

The end-to-end demonstration of the paper's system: routed expert weights
live ONLY in the compressed on-disk store; at every MoE layer the router's
top-k selection is handed to the ZipMoE engine, which reconstructs exactly
those experts (cache pools + Algorithm-1 scheduling + parallel zstd
decompression + bit-splice recovery) before the FFN runs.

Two beyond-loop mechanisms turn the I/O-bound sync path compute-centric
(DESIGN.md §3):

* **Per-step block scheduling** (§3.3 + §3.4 co-design) — every fetch is an
  ``engine.submit_step`` job whose Algorithm-1 block list orders demand
  work ahead of speculative work.  On a layer's cold/sync step the job
  combines the router's selection with the layer's *next-step* prediction
  (previous selection + FreqTracker top-k); in steady state a router
  misprediction triggers an urgent demand-only fetch that jumps the I/O
  queue and overlaps the in-flight predictions' tails.  The decode thread
  blocks ONLY on selected experts (``result_subset`` waits per-expert, a
  prediction's unused tail keeps reconstructing in the background and is
  drained to the cache pools on a later step), and new predictions exclude
  every in-flight expert, so speculative work is never duplicated.
  Hit/miss and hidden-vs-blocking wall time land in ``overlap_stats``,
  per-pool hit rates and residency transitions in ``cache_summary()``
  (optionally as a per-N-steps windowed series via ``cache_window``).
  With ``profile_p_times=True`` the block schedule sorts by *measured*
  per-expert grouped-GEMM times (``core/profiles.GemmProfiler``: measured
  on first use per (layer, expert-count, token-column) bucket, refined
  online from the real FFN wall time) instead of class constants, and with
  ``cross_layer_depth=N`` each submission carries the next N MoE layers'
  predictions in the SAME block list — the engine's p-tiering keeps demand
  ahead of near-layer predictions ahead of far-layer ones, so the I/O
  thread sequences reconstruction across layers under one priority order.
* **Slot-indexed ragged grouped FFN** (``ffn_impl="ragged"``, the default) —
  the step's tokens are CSR-concatenated by expert (each group padded only
  to the kernel's 8-row tile, the total tile count bucketed to a fixed
  shape rung) and pushed through ``kernels/ops.slab_gemm``: the megakernel
  takes the WHOLE per-layer slab buffer plus a scalar-prefetched per-tile
  slot vector and reads each expert's weights in place — no per-step
  ``jnp.take``/``jnp.stack`` weight materialisation (``w_copy_bytes`` == 0
  on a cache-hit device step, regression-tested) and no pad-to-max-C token
  FLOPs (``pad_frac`` telemetry).  The padded ``ffn_impl="grouped"`` path
  ([E_active, C, d] batch through ``moe_gemm.grouped_gemm``) and the
  per-token ``"loop"`` oracle remain as pinned-equal fallbacks.  With
  ``fused_recovery=True`` the engine hands back raw bit-planes and ONE
  batched ``zip_gemm_grouped`` launch per projection splices them to bf16
  on VREGs inside the GEMM, skipping the recovered weight's HBM round-trip.
* **Device-resident expert slabs** (``device_cache=True``) — the F pool
  lives on the accelerator: a demand miss uploads the two u8 planes once
  and the decode thread's slab reconcile lands the bit-splice directly in
  a ``core/slab.DeviceSlabCache`` slot through ONE input/output-aliased
  kernel launch (fused splice-admit: recovery warms the slab as a side
  effect), and the ragged FFN reads the slab in place by slot index — a
  fully cache-hit decode step moves **zero** expert-weight bytes
  host→device and stages **zero** weight-copy bytes
  (``overlap_summary()['h2d_bytes']`` / ``['w_copy_bytes']``,
  regression-tested).  A *mixed* step (slab rows beside fresh
  reconstructions no slot took) stages each layer's weight stacks in ONE
  jitted call (``_stage_rows``: the fresh rows stacked, each slab row
  copied in by slot, compiled once per count of selected experts)
  instead of a slot read per expert and a stack per tensor.
* **Byte-budgeted live pool planning** (``mem_budget=...``) — instead of
  fixed per-layer expert counts, one global byte budget is split across
  MoE layers by observed activity and each layer's F/C/S/E partition is
  solved by the §3.4 planner on its live rank statistics, real per-expert
  residency costs (tensor shapes + codec state sizes), and per-layer
  profiled u/c.  Every ``replan_every`` steps a windowed hit-rate probe
  detects drift and re-plans; plans apply atomically between steps
  (graceful pool shrink, churn-free grow, device slabs re-sized from the
  planned F-pool *bytes* — a cold layer's slab is freed entirely).
  ``plan_summary()`` reports per-layer plans, replan events, and byte
  occupancy.

``ZipServer.decode_step`` is validated against the fully-resident
``models.decode_step`` (bit-equal routing; identical logits up to dtype
noise) in tests/test_engine_zipserve.py, and the prefetch / grouped-FFN
paths against the synchronous / per-expert-loop paths in
tests/test_overlap_serving.py.

Scale note (DESIGN.md §6): on a TPU pod the serving path keeps experts
HBM-resident and EP-sharded; this host-driven path is the memory-constrained
single-host mode the paper targets, and doubles as the correctness harness
for the store/engine/scheduler stack.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import FetchHandle, ZipMoEEngine
from repro.core.faults import FetchError, FetchTimeout, StepFault
from repro.core.profiles import GemmProfiler
from repro.core.slab import SlotRef
from repro.core.spans import span
from repro.core.store import ExpertStore
from repro.kernels.moe_gemm import pick_block
from repro.kernels.ops import (bucket_rows, fused_zip_gemm,
                               grouped_expert_gemm, slab_gemm, zip_gemm_batch)
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models.layers import apply_mlp, apply_norm
from repro.models.model import init_cache
from repro.models.moe import route
from repro.serving.kv_cache import unstack_layers


@dataclass
class BitPlanes:
    """A tensor kept as its ZipMoE bit-planes (fused-recovery mode)."""
    exp: np.ndarray          # u8, flat
    sm: np.ndarray           # u8, flat
    shape: Tuple[int, ...]


def _planes_recover(exp: np.ndarray, sm, shape) -> BitPlanes:
    """Engine recover hook that skips the splice: zip_gemm fuses it."""
    sm_arr = (np.frombuffer(sm, np.uint8)
              if isinstance(sm, (bytes, bytearray)) else np.asarray(sm))
    return BitPlanes(np.asarray(exp), sm_arr, tuple(shape))


@jax.jit
def _stage_rows(bufs, rows, fresh):  # hot-path
    """A mixed layer-step's stacked weight sources in ONE program.

    ``bufs``: the layer slab's buffers, one per tensor name (read, never
    donated); ``rows``: int32 ``[names, n]``, a row's slab slot or -1 where
    the row is fresh; ``fresh``: per name, n ``[*shape]`` operands (the
    fresh arrays, a shared placeholder on slab rows).  Returns per name the
    ``[n, *shape]`` stack in row order: row i is ``bufs[name][rows[i]]`` or
    ``fresh[name][i]``, copied bitwise.  The compiled shapes follow n and
    the slab alone, not how many rows are fresh.

    The operands are stacked once, then a loop overwrites each slab row in
    place with one dynamic slice of its slot: no gather, whose TPU lowering
    copies the whole slab, and no n-way select, which compiles slowly."""
    def stage(buf, r, ops):
        def put(i, st):
            return jax.lax.cond(
                r[i] >= 0,
                lambda st: jax.lax.dynamic_update_index_in_dim(
                    st, jax.lax.dynamic_index_in_dim(buf, r[i], 0, False),
                    i, 0),
                lambda st: st, st)
        # host-sync-ok: traced stack of device operands, no upload
        st = jnp.stack(ops)
        return jax.lax.fori_loop(0, len(ops), put, st)
    return tuple(stage(*a) for a in zip(bufs, rows, fresh))


def _fresh_operands(vals, buf) -> tuple:
    """One name's per-row ``_stage_rows`` operands: each fresh array as is,
    each SlotRef row a shared placeholder of the tensor's shape (the name's
    first fresh array, else the slab's slot 0), overwritten in the stack."""
    ph = next((v for v in vals if not isinstance(v, SlotRef)), None)
    if ph is None:
        ph = buf[0]
    return tuple(ph if isinstance(v, SlotRef) else v for v in vals)


class ZipServer:
    # cross_layer_depth="auto" tuning knobs: adjust once per window of
    # decode steps; deepen while < RAISE_BELOW of fetch time is hidden,
    # shallow out above LOWER_ABOVE (see _tune_depth)
    _DEPTH_WINDOW = 8
    _DEPTH_RAISE_BELOW = 0.90
    _DEPTH_LOWER_ABOVE = 0.98

    def __init__(self, params, cfg, store_path: str, *, L: int = 4,
                 pool_sizes: Optional[Dict[str, int]] = None,
                 bandwidth_gbps: Optional[float] = None,
                 use_pallas_recovery: bool = False,
                 prefetch: bool = True, prefetch_width: Optional[int] = None,
                 ffn_impl: str = "ragged", fused_recovery: bool = False,
                 cache_mode: str = "hier", flat_capacity: Optional[int] = None,
                 flat_policy: str = "lru", delta: int = 1,
                 profile_p_times: bool = False, cross_layer_depth=0,
                 freq_decay: float = 1.0, cache_window: int = 0,
                 device_cache: bool = False,
                 mem_budget: Optional[float] = None,
                 replan_every: int = 32, plan_step: float = 0.125,
                 budget_split: str = "proportional",
                 mesh_devices: int = 1, peer_budget: Optional[float] = None,
                 verify: Optional[bool] = None, faults=None,
                 fetch_deadline_s: Optional[float] = 120.0):
        assert ffn_impl in ("ragged", "grouped", "loop")
        # "auto": start synchronous and let the observed hidden-fetch
        # fraction tune the depth online (see _tune_depth)
        self._auto_depth = cross_layer_depth == "auto"
        if self._auto_depth:
            cross_layer_depth = 0
        assert cross_layer_depth >= 0
        assert not (device_cache and fused_recovery), \
            "fused_recovery keeps weights as host bit-planes; device_cache " \
            "keeps them spliced on device — pick one"
        assert mesh_devices >= 1
        assert not (mesh_devices > 1 and fused_recovery), \
            "fused_recovery payloads are host bit-planes; the peer tier " \
            "slabs hold spliced device tensors — pick one"
        self.cfg = cfg
        self.prefetch = prefetch
        self.prefetch_width = prefetch_width
        self.ffn_impl = ffn_impl
        self.fused_recovery = fused_recovery
        self.device_cache = device_cache
        self.profile_p_times = profile_p_times
        self.cross_layer_depth = cross_layer_depth
        self._depth_events: List[Dict[str, float]] = []
        self._depth_steps = 0
        self._depth_base: Optional[Dict[str, float]] = None
        peer_mesh = None
        if mesh_devices > 1:
            # single-process multi-device (e.g. XLA_FLAGS=
            # --xla_force_host_platform_device_count=N on CPU CI): the
            # compressed store + expert slabs shard over the 'ep' axis
            if jax.device_count() < mesh_devices:
                raise ValueError(
                    f"mesh_devices={mesh_devices} but only "
                    f"{jax.device_count()} visible device(s)")
            from repro.launch.mesh import make_mesh
            peer_mesh = make_mesh((mesh_devices,), ("ep",))
        self.layers = unstack_layers(params["decoder"], cfg)
        self.globals = {k: v for k, v in params.items() if k != "decoder"}
        store = ExpertStore(store_path, bandwidth_gbps=bandwidth_gbps,
                            verify=verify, faults=faults)
        recover = None
        if fused_recovery:
            recover = _planes_recover
        elif use_pallas_recovery and not device_cache \
                and ffn_impl == "loop":
            from repro.kernels.ops import recover_bf16_host
            recover = recover_bf16_host       # host-loop oracle needs numpy
        self.engine = ZipMoEEngine(
            store, n_experts=max(1, cfg.n_experts), n_layers=cfg.n_layers,
            L=L, pool_sizes=pool_sizes, recover_fn=recover,
            cache_mode=cache_mode, flat_capacity=flat_capacity,
            flat_policy=flat_policy, delta=delta, freq_decay=freq_decay,
            device_cache=device_cache, peer_mesh=peer_mesh,
            fetch_deadline_s=fetch_deadline_s)
        if use_pallas_recovery and not device_cache and ffn_impl != "loop":
            # the grouped GEMM consumes the spliced tensor on device — keep
            # it there instead of the historical device→host→device round
            # trip, via the engine's counting wrapper so the plane uploads
            # and splice time land in the h2d_bytes/splice_ms telemetry
            self.engine.recover = self.engine._recover_device
        self.engine.profile()
        if mem_budget is not None:
            # byte-budgeted live pool planning (§3.4 online): per-layer
            # plans from one global byte budget, re-planned under drift.
            # An explicit pool_sizes override keeps the static capacities
            # until the first drift-triggered re-plan.
            self.engine.configure_planner(mem_budget,
                                          replan_every=replan_every,
                                          plan_step=plan_step,
                                          initial_plan=pool_sizes is None,
                                          budget_split=budget_split,
                                          peer_budget=peer_budget)
        if cache_window:
            self.engine.enable_cache_windows(cache_window)
        # measured per-expert grouped-GEMM times feeding Algorithm 1's p_n
        # (constant-p scheduling when profile_p_times is off: p_times=None
        # falls back to the engine's class constants)
        self.profiler = GemmProfiler(default_p=ZipMoEEngine._DEMAND_P)
        self._gemm_runners: Dict[int, object] = {}   # layer -> runner|None
        # strip routed expert weights from the resident copy (they live on disk)
        for lp in self.layers:
            if "ffn" in lp and "router" in lp["ffn"]:
                for name in ("w_gate", "w_up", "w_down"):
                    lp["ffn"].pop(name, None)
        self._moe_layers = [i for i, lp in enumerate(self.layers)
                            if "ffn" in lp and "router" in lp["ffn"]]
        # per layer: live prediction jobs (handle, predicted-id set).  A step
        # waits only on the covered subset of each; finished jobs are drained
        # (tail admitted to the cache) lazily on the decode thread
        self._pending: Dict[int, List[Tuple[FetchHandle, frozenset]]] = {}
        self._last_ids: Dict[int, List[int]] = {}
        # per-request cache accounting (continuous batching): rid -> counters,
        # attributed from pure residency queries at step start so the shared
        # union-level hit/miss telemetry is never perturbed
        self.req_stats: Dict[int, Dict[str, int]] = {}
        self.stats: List[Dict] = []
        self.overlap_stats = {
            "pred_hits": 0, "pred_misses": 0, "sync_fetches": 0,
            "fetch_wall_s": 0.0,     # background wall time of prefetched jobs
            "fetch_wait_s": 0.0,     # of which the decode thread was blocked
            "blocking_s": 0.0,       # sync / fallback fetch wall time
            "fault_refetches": 0,    # demand re-fetches of failed spec work
            "tokens_real": 0,        # routed (token, expert) pairs per GEMM
            "tokens_padded": 0,      # GEMM rows actually computed (w/ pads)
            "stage_calls": 0,        # mixed layer-steps staged in one call
            "stage_rows_slab": 0,    # of their rows, read from the slab
            "stage_rows_fresh": 0,   # and taken from fresh device arrays
        }

    def close(self):
        self.engine.shutdown()

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, length: int):
        caches = unstack_layers(init_cache(self.cfg, batch, length), self.cfg)
        return caches

    # ------------------------------------------------------------------
    # expert acquisition: prefetch consumption + blocking fallback
    # ------------------------------------------------------------------
    def _next_moe_layer(self, layer_idx: int) -> Optional[int]:
        """The MoE layer whose fetch can overlap from `layer_idx` on
        (wrapping to the first MoE layer of the next decode step)."""
        if not self._moe_layers:
            return None
        for j in self._moe_layers:
            if j > layer_idx:
                return j
        return self._moe_layers[0]

    def _predict(self, layer_idx: int, batch: int, exclude) -> List[int]:
        """Predicted experts for `layer_idx`'s next decode step: the layer's
        previous-step selection (temporal locality) topped up with the
        FreqTracker's most-frequent experts."""
        width = self.prefetch_width or min(self.cfg.n_experts,
                                           batch * self.cfg.top_k
                                           + self.cfg.top_k)
        # filter exclusions DURING building so the prediction keeps its full
        # width, topping up from the frequency ranking past excluded ids
        pred = [e for e in self._last_ids.get(layer_idx, ())
                if e not in exclude]
        for e in self.engine.predict_topk(layer_idx, width + len(exclude)):
            if len(pred) >= width:
                break
            if e not in pred and e not in exclude:
                pred.append(e)
        return pred[:width]

    def _in_flight(self, layer_idx: int) -> frozenset:
        """Experts covered by this layer's live prediction jobs."""
        return frozenset().union(*(s for _, s in
                                   self._pending.get(layer_idx, [])))

    def _moe_layers_after(self, layer_idx: int, depth: int) -> List[int]:
        """Up to `depth` distinct MoE layers following `layer_idx` in decode
        order (wrapping to the next step's first MoE layer) — the layers a
        cross-layer submission extends its predictions to."""
        out: List[int] = []
        j = layer_idx
        for _ in range(depth):
            j = self._next_moe_layer(j)
            if j is None or j == layer_idx or j in out:
                break
            out.append(j)
        return out

    # ------------------------------------------------------------------
    # profiled p-times (GemmProfiler -> Algorithm 1's p_n)
    # ------------------------------------------------------------------
    def _gemm_runner(self, layer_idx: int):
        """Measurement closure for the profiler: executes one representative
        grouped FFN of this layer's expert shapes (warmup run eats the jit
        compile; the timed run is pure execution)."""
        groups = self.engine.store.groups
        experts = [e for (l, e) in groups if l == layer_idx]
        if not experts:
            return None
        shapes = {t.name: tuple(t.shape)
                  for t in groups[(layer_idx, min(experts))].tensors}
        if "w_up" not in shapes or "w_down" not in shapes:
            return None

        def run(ne: int, cols: int) -> float:
            rng = np.random.default_rng(0)
            d, f = shapes["w_up"]
            x = jnp.asarray(rng.standard_normal((ne, cols, d)),
                            jnp.bfloat16)
            wu = jnp.asarray(rng.standard_normal((ne, d, f)), jnp.bfloat16)
            wd = jnp.asarray(rng.standard_normal((ne, f, d)), jnp.bfloat16)
            wg = jnp.asarray(rng.standard_normal((ne, d, f)),
                             jnp.bfloat16) if "w_gate" in shapes else None
            gg = lambda a, w: grouped_expert_gemm(
                a, w, block_c=pick_block(cols, 128),
                block_d=pick_block(a.shape[-1], 512),
                block_f=pick_block(w.shape[-1], 128))

            def once():
                h = jax.nn.silu(gg(x, wg)) * gg(x, wu) if wg is not None \
                    else jax.nn.gelu(gg(x, wu))
                return gg(h, wd)

            jax.block_until_ready(once())          # compile warmup
            t0 = time.perf_counter()
            jax.block_until_ready(once())
            return time.perf_counter() - t0

        return run

    def _exec_group_size(self, layer_idx: int, batch: int) -> int:
        """Expected number of experts that execute *together* in one of this
        layer's decode steps — the profiler's bucket key.  p_n is the
        per-expert share of a grouped GEMM, so every id of a submission
        (demand and predictions alike) is priced at the group size it will
        run in, NOT at the submission's total id count: the step's last
        observed selection size, falling back to the batch top-k bound."""
        last = self._last_ids.get(layer_idx)
        if last:
            return len(last)
        return max(1, min(self.cfg.n_experts, batch * self.cfg.top_k))

    def _p_times_for(self, layer_idx: int, ids: List[int], batch: int
                     ) -> Optional[Dict[int, float]]:
        """Measured per-expert p_n for one submission part, or None for the
        engine's class constants (constant-p scheduling).  The measurement
        runner is built once per layer and only handed over when the bucket
        is not yet cached — this sits on the decode hot path."""
        if not self.profile_p_times or not ids:
            return None
        cols = max(1, batch * self.cfg.top_k)
        group = self._exec_group_size(layer_idx, batch)
        runner = None
        if not self.profiler.has(layer_idx, group, cols):
            if layer_idx not in self._gemm_runners:
                self._gemm_runners[layer_idx] = self._gemm_runner(layer_idx)
            runner = self._gemm_runners[layer_idx]
        p = self.profiler.p_time(layer_idx, group, cols, runner=runner)
        return {int(e): p for e in ids}

    def _drain(self, layer_idx: int) -> int:
        """Collect finished prediction jobs of `layer_idx` on the decode
        thread: their unused tails are admitted to the cache pools (warming
        them) and leave the in-flight set, so they become predictable again
        as cheap resident no-op tasks.  Returns the drained io_bytes.

        A cross-layer job appears in every covered layer's pending list;
        ``spec_result()`` caches, and the stats are credited only on the
        first drain (the flag guard), so multi-list membership never
        double-counts wall time or bytes."""
        ov = self.overlap_stats
        live, io = [], 0
        with span("zipmoe.fetch.drain", layer=layer_idx):
            for h, s in self._pending.get(layer_idx, []):
                if h.done():
                    _, st = h.spec_result()  # background work: fully hidden
                    if not getattr(h, "_drained_stats", False):
                        h._drained_stats = True
                        ov["fetch_wall_s"] += st.wall
                        io += st.io_bytes
                else:
                    live.append((h, s))
        if layer_idx in self._pending:
            self._pending[layer_idx] = live
        return io

    def _issue_step(self, layer_idx: int, demand_ids: List[int], batch: int):
        """One Algorithm-1 step submission anchored at `layer_idx`: the
        demand ids (this step's selection still missing from every pending
        prediction) plus the layer's next-step prediction, under a single
        block schedule.  With ``cross_layer_depth > 0`` the same submission
        also carries predictions for the next MoE layers in decode order —
        ONE block list spans all covered layers, the engine's p-tiering
        keeps demand ahead of near-layer predictions ahead of far-layer
        ones, and the job registers in every covered layer's pending list.
        In-flight experts are excluded from every layer's prediction (their
        job already reconstructs them — no duplicate work) but stay covered
        through their own pending entry."""
        with span("zipmoe.prefetch_submit", layer=layer_idx):
            pred = (self._predict(layer_idx, batch, set(demand_ids)
                                  | self._in_flight(layer_idx))
                    if self.prefetch else [])
            parts = []
            if demand_ids or pred:
                parts.append((layer_idx, demand_ids, pred,
                              self._p_times_for(layer_idx,
                                                list(demand_ids) + pred,
                                                batch)))
            extra: List[Tuple[int, List[int]]] = []
            if self.prefetch and self.cross_layer_depth:
                for j in self._moe_layers_after(layer_idx,
                                                self.cross_layer_depth):
                    pred_j = self._predict(j, batch, self._in_flight(j))
                    if pred_j:
                        parts.append((j, [], pred_j,
                                      self._p_times_for(j, pred_j, batch)))
                        extra.append((j, pred_j))
            if not parts:
                return None
            h = self.engine.submit_steps(parts)
            if self.prefetch:
                # the demand half counts as predicted for the NEXT step too:
                # it is reconstructed by this very job, so a re-selected
                # expert is a prediction hit, never a sticky demand refetch
                if demand_ids or pred:
                    self._pending.setdefault(layer_idx, []).append(
                        (h, frozenset(pred) | set(demand_ids)))
                for j, pred_j in extra:
                    self._pending.setdefault(j, []).append(
                        (h, frozenset(pred_j)))
            return h

    def _issue_prefetch(self, layer_idx: Optional[int], batch: int):
        """Cold-start speculative submission (no demand half) for a layer
        that has no pending step job yet."""
        if layer_idx is None or not self.prefetch \
                or self._pending.get(layer_idx):
            return
        self._issue_step(layer_idx, [], batch)

    def _acquire_experts(self, layer_idx: int, ids: List[int], batch: int):
        """Expert weights for `ids`, consuming the pending prediction jobs.

        Returns (weights, io_bytes, blocked_s) where blocked_s is the wall
        time the decode thread actually spent waiting on reconstruction —
        only the selected experts are waited on, never a prediction job's
        unused tail (that keeps reconstructing in the background and is
        drained on a later step).
        """
        ov = self.overlap_stats
        pend = list(self._pending.get(layer_idx, []))
        if not pend:
            # no prediction in flight: everything is demand; the same
            # submission still carries the layer's next-step prediction
            h = self._issue_step(layer_idx, ids, batch)
            weights, fstats = h.result()
            ov["sync_fetches"] += 1
            ov["blocking_s"] += fstats.wall
            return weights, fstats.io_bytes, fstats.wall
        io_bytes = 0
        in_flight = self._in_flight(layer_idx)
        covered = [e for e in ids if e in in_flight]
        missing = [e for e in ids if e not in in_flight]
        # pin the WHOLE selection for the step (pins are refcounted, so a
        # pending job releasing its own pin on the same expert cannot
        # release ours; the missing half's submit below also pins, but its
        # job pins release at collection — before the drain, whose
        # admissions must still not evict any selected expert) and record
        # the access BEFORE any of this step's admissions, so hit/miss
        # telemetry reflects residency at step start (the demand fallback
        # records its own at submit)
        self.engine.pin_experts(layer_idx, ids)
        self.engine.note_access(layer_idx, covered)
        # a misprediction's demand fetch is submitted BEFORE waiting on the
        # prediction jobs: `missing` is disjoint from every in-flight
        # prediction by construction (no duplicate work is possible), and
        # the urgent job jumps the I/O queue so it overlaps their tails
        h_m = None
        if missing:
            with span("zipmoe.prefetch_submit", layer=layer_idx):
                h_m = self.engine.prefetch_experts(
                    layer_idx, missing,
                    self._p_times_for(layer_idx, missing, batch))
        if h_m is not None and self.prefetch:
            # the fallback job joins the pending list like any submission:
            # its experts are in flight, so the end-of-step prediction won't
            # re-fetch them even if tiny pools evict them on admission
            self._pending.setdefault(layer_idx, []).append(
                (h_m, frozenset(missing)))
        t0 = time.perf_counter()     # CPU-side submit cost stays excluded
        weights: Dict[int, Dict] = {}
        try:
            remaining = set(covered)
            for h, s in pend:
                take = [e for e in remaining if e in s]
                if not take:
                    continue
                remaining.difference_update(take)
                # blocks on `take` of THIS layer only — never on the job's
                # other layers' speculative tails
                w, st = h.result_subset(take, layer=layer_idx)
                weights.update(w)
                ov["fetch_wall_s"] += st.wall
                ov["fetch_wait_s"] += h.wait_s
                io_bytes += st.io_bytes
            if h_m is not None:
                ov["pred_misses"] += 1
                extra, fs2 = h_m.result()
                weights.update(extra)
                io_bytes += fs2.io_bytes
                # the fallback ran concurrently with the speculative tails:
                # only the time actually blocked in result() is un-hidden
                ov["fetch_wall_s"] += fs2.wall
                ov["fetch_wait_s"] += h_m.wait_s
            else:
                ov["pred_hits"] += 1
            # graceful degradation: a selected expert whose SPECULATIVE
            # fetch failed is dropped by result_subset (counted in the
            # engine's spec_drops) — re-fetch it on demand through a fresh
            # job, which retries the whole read path.  Only a persistent
            # fault raises from result() here (strict demand collection).
            lost = [e for e in ids if e not in weights]
            if lost:
                ov["fault_refetches"] += 1
                h_r = self.engine.prefetch_experts(
                    layer_idx, lost, self._p_times_for(layer_idx, lost,
                                                       batch))
                w_r, fs_r = h_r.result()
                weights.update(w_r)
                io_bytes += fs_r.io_bytes
                ov["blocking_s"] += fs_r.wall
            blocked = time.perf_counter() - t0
            # drain finished prediction jobs AFTER they served this step's
            # coverage: their unused tails are admitted to the cache and
            # leave the in-flight set, then the next step's prediction
            # excludes every still-in-flight expert (no duplicate fetches)
            # and may re-include drained residents, which become F-state
            # no-op tasks.  The step pins are still held through the drain —
            # its admissions must never evict a selected expert before the
            # FFN consumes it (in device_cache mode an eviction would free
            # the expert's slab slot under the weights this function is
            # about to return)
            io_bytes += self._drain(layer_idx)
        finally:
            # on the failure path too: an unreleased step pin would leak
            # and permanently shield the expert from eviction
            self.engine.unpin_experts(layer_idx, ids)
        self._issue_step(layer_idx, [], batch)
        return weights, io_bytes, blocked

    def overlap_summary(self) -> Dict[str, float]:
        """Fetch time hidden under compute / total fetch wall time, plus
        the host↔device weight-traffic counters (``h2d_bytes`` /
        ``splice_ms`` etc. — zero h2d on a fully cache-hit device-mode
        step; see ``engine.transfer_summary``)."""
        ov = self.overlap_stats
        total = ov["fetch_wall_s"] + ov["blocking_s"]
        hidden = ov["fetch_wall_s"] - ov["fetch_wait_s"]
        padded = ov["tokens_padded"]
        return {**ov, **self.engine.transfer_summary(),
                "total_fetch_s": total, "hidden_fetch_s": hidden,
                "hidden_frac": hidden / total if total > 0 else 0.0,
                # fraction of expert-GEMM token FLOPs spent on padding rows
                # (the ragged path's win over pad-to-max-C)
                "pad_frac": (padded - ov["tokens_real"]) / padded
                            if padded > 0 else 0.0,
                "cross_layer_depth": self.cross_layer_depth,
                "auto_depth": self._auto_depth,
                "depth_events": list(self._depth_events)}

    def peer_summary(self) -> Dict[str, object]:
        """Peer-HBM (P tier) telemetry: link-served vs fallback counts,
        collective-traffic ledger, profiled link model, and per-layer slab
        occupancy.  ``{"enabled": False}`` without a mesh."""
        return self.engine.peer_summary()

    def fault_summary(self) -> Dict[str, object]:
        """Failure-handling telemetry: engine counters (worker restarts,
        deadline hits, spec drops, fallback loads, failed experts), store
        integrity counters (retries, checksum failures, quarantined
        chunks), injected-fault firings when a :class:`FaultPlan` is
        active, and the serving layer's demand re-fetches of failed
        speculative work."""
        out = self.engine.fault_summary()
        out["fault_refetches"] = self.overlap_stats["fault_refetches"]
        return out

    def _tune_depth(self):
        """Auto-tune ``cross_layer_depth`` from the observed hidden-fetch
        fraction (``cross_layer_depth="auto"``).

        Every window of decode steps, look at the fetch time accrued since
        the last adjustment: if a meaningful share of it blocked the decode
        thread, prediction is not being issued early enough — deepen the
        cross-layer horizon so fetches start more layers ahead.  If
        essentially everything was hidden, try a shallower horizon (less
        speculative traffic for the same overlap).  Bounds: [0, #MoE
        layers]; each change is logged in ``depth_events`` and surfaced by
        :meth:`overlap_summary`."""
        self._depth_steps += 1
        if self._depth_steps % self._DEPTH_WINDOW:
            return
        ov = self.overlap_stats
        cur = {"fetch_wall_s": ov["fetch_wall_s"],
               "fetch_wait_s": ov["fetch_wait_s"],
               "blocking_s": ov["blocking_s"]}
        base = self._depth_base or {k: 0.0 for k in cur}
        self._depth_base = cur
        wall = cur["fetch_wall_s"] - base["fetch_wall_s"]
        wait = cur["fetch_wait_s"] - base["fetch_wait_s"]
        blocked = cur["blocking_s"] - base["blocking_s"]
        total = wall + blocked
        if total <= 0.0:                  # all-hit window: nothing to tune
            return
        hidden_frac = max(0.0, wall - wait) / total
        depth = self.cross_layer_depth
        if hidden_frac < self._DEPTH_RAISE_BELOW:
            depth = min(depth + 1, len(self._moe_layers))
        elif hidden_frac > self._DEPTH_LOWER_ABOVE:
            depth = max(depth - 1, 0)
        if depth != self.cross_layer_depth:
            self._depth_events.append({
                "step": float(self._depth_steps),
                "from": float(self.cross_layer_depth),
                "to": float(depth), "hidden_frac": hidden_frac})
            self.cross_layer_depth = depth

    def cache_summary(self, per_layer: bool = False,
                      windows: bool = False) -> Dict[str, object]:
        """Live §3.4 cache telemetry (per-pool hit rates, residency-state
        transition counts, evictions) — the cache-side complement to
        :meth:`overlap_summary`.  ``windows=True`` appends the per-N-steps
        delta series when the server was built with ``cache_window=N``."""
        return self.engine.cache_summary(per_layer=per_layer,
                                         windows=windows)

    def p_time_summary(self) -> Dict[str, object]:
        """Measured p-time buckets feeding Algorithm 1 (empty when
        ``profile_p_times`` is off)."""
        return self.profiler.summary()

    def plan_summary(self) -> Dict[str, object]:
        """Live §3.4 planning telemetry (``mem_budget`` mode): per-layer
        plans, replan events, and byte occupancy — next to
        :meth:`cache_summary` / :meth:`overlap_summary`."""
        return self.engine.plan_summary()

    # ------------------------------------------------------------------
    # expert FFN implementations
    # ------------------------------------------------------------------
    def _ffn_loop(self, x, top_p, top_i, weights):
        """Reference per-batch/per-slot loop (validation oracle)."""
        cfg = self.cfg
        B = x.shape[0]
        y = jnp.zeros_like(x)
        for b in range(B):
            acc = jnp.zeros((1, 1, x.shape[-1]), x.dtype)
            for slot in range(cfg.top_k):
                e = int(top_i[b, 0, slot])
                w = {k: self._as_weight(v) for k, v in weights[e].items()}
                xb = x[b:b + 1]
                h = jax.nn.silu(xb @ w["w_gate"]) * \
                    (xb @ w["w_up"]) if "w_gate" in w else \
                    jax.nn.gelu(xb @ w["w_up"])
                acc = acc + top_p[b, 0, slot].astype(x.dtype) * \
                    (h @ w["w_down"])
            y = y.at[b:b + 1].set(acc)
        return y

    def _assign_by_expert(self, top_p, top_i, ids):
        """Per-expert (token row, gate) lists in ``ids`` order — the shared
        CSR front half of both gather builders."""
        cfg = self.cfg
        ti = np.asarray(top_i)
        tp = np.asarray(top_p, np.float32)
        B = ti.shape[0]
        ti = ti.reshape(B, cfg.top_k)
        tp = tp.reshape(B, cfg.top_k)
        row = {e: r for r, e in enumerate(ids)}
        assign: List[List[Tuple[int, float]]] = [[] for _ in ids]
        for b in range(B):
            for slot in range(cfg.top_k):
                assign[row[int(ti[b, slot])]].append((b, float(tp[b, slot])))
        return assign, B

    def _gather_by_expert(self, top_p, top_i, ids):
        """Token->expert assignment tables for the PADDED grouped batch.

        Returns (gather [Ea, C] int32 token rows, padded with B;
                 gates [Ea, C] f32 routing weights).  C is the max group
        size bucketed to a fixed shape rung (``bucket_rows``) so decode
        steps reuse a handful of jit entries instead of recompiling on
        every routing-skew change.
        """
        assign, B = self._assign_by_expert(top_p, top_i, ids)
        C = bucket_rows(max(len(a) for a in assign))
        gather = np.full((len(ids), C), B, np.int32)   # B = zero-pad token
        gates = np.zeros((len(ids), C), np.float32)
        for r, a in enumerate(assign):
            for c, (b, g) in enumerate(a):
                gather[r, c] = b
                gates[r, c] = g
        self.overlap_stats["tokens_real"] += sum(len(a) for a in assign)
        self.overlap_stats["tokens_padded"] += len(ids) * C
        return gather, gates

    def _gather_by_expert_ragged(self, top_p, top_i, ids, block_c: int = 8):
        """CSR token->expert tables for the slot-indexed ragged GEMM.

        Token rows are concatenated group by group (``ids`` order); each
        group is padded only to a ``block_c``-row tile boundary (a tile
        must not straddle experts), and the TOTAL tile count is bucketed to
        a fixed rung.  Pad rows aim at the zero token B with gate 0 and
        tiles past the last group at expert row 0 (any valid slot), so they
        contribute nothing.  Returns (gather [T] int32, gates [T] f32,
        tile_row [T/block_c] int32 rows into ``ids``).
        """
        assign, B = self._assign_by_expert(top_p, top_i, ids)
        tiles = [-(-max(len(a), 1) // block_c) for a in assign]
        n_tiles = bucket_rows(sum(tiles), align=1)
        T = n_tiles * block_c
        gather = np.full(T, B, np.int32)               # B = zero-pad token
        gates = np.zeros(T, np.float32)
        tile_row = np.zeros(n_tiles, np.int32)
        t = 0
        for r, a in enumerate(assign):
            tile_row[t // block_c: t // block_c + tiles[r]] = r
            for b, g in a:
                gather[t] = b
                gates[t] = g
                t += 1
            t = -(-t // block_c) * block_c             # next tile boundary
        self.overlap_stats["tokens_real"] += sum(len(a) for a in assign)
        self.overlap_stats["tokens_padded"] += T
        return gather, gates, tile_row

    def _as_weight(self, v) -> jnp.ndarray:
        """One expert tensor as a device array: slab slots read in place,
        device arrays pass through, host ndarrays pay (and are charged) an
        upload."""
        if isinstance(v, SlotRef):
            return v.read()
        if isinstance(v, np.ndarray):
            self.engine.count_h2d(v.nbytes)
        return jnp.asarray(v)

    def _stack_weights(self, name: str, weights, ids) -> jnp.ndarray:  # hot-path
        """[Ea, ...] stacked expert weights for the grouped GEMM.

        The device-cache fast path: when every selected expert is resident
        in the SAME layer slab, one ``jnp.take`` gathers the stack straight
        from the device buffer — zero weight bytes cross host→device.
        Mixed steps (a fresh reconstruction not yet slab-admitted rides
        along as a plain device array) fall back to a device-side stack;
        host ndarrays (host mode) pay the historical per-step re-upload,
        charged to the engine's ``h2d_bytes`` so the before/after is
        measurable."""
        vals = [weights[e][name] for e in ids]
        if vals and all(isinstance(v, SlotRef) for v in vals):
            slab = vals[0].slab
            # validity is part of the fast-path condition: a stale ref must
            # never be silently gathered as the slot's NEW occupant — it
            # falls through to _as_weight, whose read() asserts (a crash
            # tripwire for slot-lifecycle bugs, not a corruption)
            if all(v.slab is slab and v.valid for v in vals):
                w = slab.gather(name, [v.slot for v in vals])
                self.engine.count_w_copy(int(w.size) * w.dtype.itemsize)
                return w
        # host-sync-ok: fallback — host/mixed steps pay the re-upload (h2d_bytes)
        w = jnp.stack([self._as_weight(v) for v in vals])
        self.engine.count_w_copy(int(w.size) * w.dtype.itemsize)
        return w

    def _slab_sources(self, names, weights, ids):  # hot-path
        """``{name: (buffer, slots)}`` weight sources of one layer-step for
        the slot-indexed ragged GEMM (``names``: the tensor names, or one
        name as a str).

        Zero-copy fast path: every selected expert's tensors are valid
        SlotRefs into the SAME layer slab — return the slab's buffers
        themselves (read in place by the megakernel) plus the per-expert
        slot vectors; no weight bytes move, nothing is charged.

        Mixed step (some experts are fresh device arrays no slot took):
        ONE call of :func:`_stage_rows` builds every name's stacked
        ``[Ea, ...]`` batch, slab rows read by slot inside the program,
        indexed by stack row and charged to ``w_copy_bytes``.  Host ndarrays
        (host mode) and anything else pay a per-name upload-and-stack; a
        stale SlotRef reaches its ``read()`` there and trips."""
        if isinstance(names, str):
            names = (names,)
        vals = {n: [weights[e][n] for e in ids] for n in names}
        refs = [v for vs in vals.values() for v in vs if isinstance(v, SlotRef)]
        slab = refs[0].slab if refs else None
        in_slab = slab is not None and \
            all(v.slab is slab and v.valid for v in refs)
        if in_slab and len(refs) == len(names) * len(ids):
            # host-sync-ok: host slot-index vectors, no transfer
            return {n: (slab.bufs[n], np.asarray([v.slot for v in vals[n]],
                                                 np.int32))
                    for n in names}
        order = np.arange(len(ids), dtype=np.int32)
        if in_slab and all(isinstance(v, jax.Array) for vs in vals.values()
                           for v in vs if not isinstance(v, SlotRef)):
            # host-sync-ok: host row-source table (slot, or -1: fresh)
            rows = np.asarray([[v.slot if isinstance(v, SlotRef) else -1
                                for v in vals[n]] for n in names], np.int32)
            stacks = _stage_rows(tuple(slab.bufs[n] for n in names), rows,
                                 tuple(_fresh_operands(vals[n], slab.bufs[n])
                                       for n in names))
            from_slab = int((rows[0] >= 0).sum())
            ov = self.overlap_stats
            ov["stage_calls"] += 1
            ov["stage_rows_slab"] += from_slab
            ov["stage_rows_fresh"] += len(ids) - from_slab
        else:
            # host-sync-ok: host/unslabbed steps stage an uploaded copy
            stacks = [jnp.stack([self._as_weight(v) for v in vals[n]])
                      for n in names]
        self.engine.count_w_copy(sum(int(w.size) * w.dtype.itemsize
                                     for w in stacks))
        return {n: (w, order) for n, w in zip(names, stacks)}

    def _ffn_grouped(self, x, top_p, top_i, weights, ids):  # hot-path
        """Gather-by-expert batched FFN on the grouped-GEMM kernel."""
        B, _, d = x.shape
        gather, gates = self._gather_by_expert(top_p, top_i, ids)
        xf = x.reshape(B, d)
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
        xg = xpad[gather]                                   # [Ea, C, d]

        def stack(name):
            return self._stack_weights(name, weights, ids)

        C = xg.shape[1]
        gg = lambda a, w: grouped_expert_gemm(
            a, w, block_c=pick_block(C, 128), block_d=pick_block(a.shape[-1], 512),
            block_f=pick_block(w.shape[-1], 128))
        if "w_gate" in weights[ids[0]]:
            h = jax.nn.silu(gg(xg, stack("w_gate"))) * gg(xg, stack("w_up"))
        else:
            h = jax.nn.gelu(gg(xg, stack("w_up")))
        eout = gg(h, stack("w_down"))                       # [Ea, C, d]
        comb = jnp.zeros((B + 1, d), jnp.float32).at[
            jnp.asarray(gather.reshape(-1))].add(
            jnp.asarray(gates.reshape(-1, 1)) *
            eout.reshape(-1, d).astype(jnp.float32))
        return comb[:B].astype(x.dtype).reshape(B, 1, d)

    def _ffn_ragged(self, x, top_p, top_i, weights, ids):  # hot-path
        """Slot-indexed ragged grouped FFN — the megakernel hot path.

        Tokens ride in CSR order (per-group tile padding only, total tile
        count bucketed); the per-tile slot vector is scalar-prefetched and
        the kernel reads each expert's weights straight out of the slab
        buffer — zero weight-copy bytes on the all-slab-resident fast path
        (``_slab_sources``).  Bit-identical to ``_ffn_grouped``: per-row
        GEMM results are blocking-invariant and the scatter-add combine
        sees the same per-destination contribution order (group order and
        in-group token order match; pad rows only ever touch token B)."""
        B, _, d = x.shape
        block_c = 8
        names = [n for n in ("w_gate", "w_up", "w_down")
                 if n in weights[ids[0]]]
        with span("zipmoe.ffn.stage"):
            gather, gates, tile_row = self._gather_by_expert_ragged(
                top_p, top_i, ids, block_c)
            xf = x.reshape(B, d)
            xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
            xg = xpad[jnp.asarray(gather)]                 # [T, d]
            src = self._slab_sources(names, weights, ids)

        def sg(a, name):                                   # one megakernel
            buf, slots = src[name]
            return slab_gemm(a, buf, slots[tile_row], block_c=block_c,
                             block_d=pick_block(a.shape[-1], 512),
                             block_f=pick_block(buf.shape[-1], 128))

        with span("zipmoe.ffn.gemm"):
            if "w_gate" in src:
                h = jax.nn.silu(sg(xg, "w_gate")) * sg(xg, "w_up")
            else:
                h = jax.nn.gelu(sg(xg, "w_up"))
            eout = sg(h, "w_down")                         # [T, d]
            comb = jnp.zeros((B + 1, d), jnp.float32).at[
                jnp.asarray(gather)].add(
                jnp.asarray(gates[:, None]) * eout.astype(jnp.float32))
            return comb[:B].astype(x.dtype).reshape(B, 1, d)

    def _ffn_zip_gemm(self, x, top_p, top_i, weights, ids):
        """Fused recovery+GEMM, ONE batched launch per projection: expert
        weights stay u8 bit-planes and ``zip_gemm_grouped`` splices them to
        bf16 on VREGs right before the MXU, for every active expert of the
        step at once (the historical per-expert Python loop survives as
        ``_ffn_zip_loop``, selected by ``ffn_impl="loop"``).  Plane uploads
        are charged to ``h2d_bytes``."""
        B, _, d = x.shape
        gather, gates = self._gather_by_expert(top_p, top_i, ids)
        xf = x.reshape(B, d).astype(jnp.bfloat16)
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
        xg = xpad[jnp.asarray(gather)]                      # [Ea, C, d]
        C = xg.shape[1]

        def planes(name):
            ps: List[BitPlanes] = [weights[e][name] for e in ids]
            D, F = ps[0].shape
            exp = np.stack([p.exp.reshape(D, F) for p in ps])
            sm = np.stack([p.sm.reshape(D, F) for p in ps])
            self.engine.count_h2d(exp.nbytes + sm.nbytes)
            return jnp.asarray(exp), jnp.asarray(sm)

        def zg(a, pl):
            exp, sm = pl
            return zip_gemm_batch(a, exp, sm,
                                  block_c=pick_block(C, 128),
                                  block_d=pick_block(exp.shape[1], 512),
                                  block_f=pick_block(exp.shape[2], 128))

        if "w_gate" in weights[ids[0]]:
            h = jax.nn.silu(zg(xg, planes("w_gate"))) * zg(xg, planes("w_up"))
        else:
            h = jax.nn.gelu(zg(xg, planes("w_up")))
        eout = zg(h.astype(jnp.bfloat16), planes("w_down"))  # [Ea, C, d]
        comb = jnp.zeros((B + 1, d), jnp.float32).at[
            jnp.asarray(gather.reshape(-1))].add(
            jnp.asarray(gates.reshape(-1, 1)) *
            eout.reshape(-1, d).astype(jnp.float32))
        return comb[:B].astype(x.dtype).reshape(B, 1, d)

    def _ffn_zip_loop(self, x, top_p, top_i, weights, ids):
        """Per-expert fused recovery+GEMM loop (pre-batching fallback,
        pinned equal to :meth:`_ffn_zip_gemm` by tests)."""
        B, _, d = x.shape
        gather, gates = self._gather_by_expert(top_p, top_i, ids)
        xf = x.reshape(B, d).astype(jnp.bfloat16)
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])

        def zg(a, planes: BitPlanes):
            D, F = planes.shape
            return fused_zip_gemm(
                a, jnp.asarray(planes.exp).reshape(D, F),
                jnp.asarray(planes.sm).reshape(D, F),
                block_c=pick_block(a.shape[0], 128),
                block_d=pick_block(D, 512), block_f=pick_block(F, 128))

        comb = jnp.zeros((B + 1, d), jnp.float32)
        for r, e in enumerate(ids):   # loop-ok: validation fallback path
            w = weights[e]
            xe = xpad[gather[r]]                            # [C, d]
            if "w_gate" in w:
                h = jax.nn.silu(zg(xe, w["w_gate"])) * zg(xe, w["w_up"])
            else:
                h = jax.nn.gelu(zg(xe, w["w_up"]))
            out = zg(h.astype(jnp.bfloat16), w["w_down"])   # [C, d]
            comb = comb.at[jnp.asarray(gather[r])].add(
                jnp.asarray(gates[r][:, None]) * out.astype(jnp.float32))
        return comb[:B].astype(x.dtype).reshape(B, 1, d)

    def _note_request_access(self, layer_idx: int, top_i, owners):
        """Per-request hit attribution under the multi-tenant union: row
        ``b``'s owner is charged one access per routed expert, a hit when
        that expert was resident at step start.  Pure ``residency``
        queries — the shared union-level record_access stats (one tally
        per unique expert per step) are untouched."""
        ti = np.asarray(top_i).reshape(len(owners), self.cfg.top_k)
        states = self.engine.residency_states(
            layer_idx, {int(e) for e in ti.reshape(-1)})
        for b, rid in enumerate(owners):
            st = self.req_stats.setdefault(
                rid, {"accesses": 0, "hits": 0, "steps": 0})
            for e in {int(v) for v in ti[b]}:
                st["accesses"] += 1
                st["hits"] += int(states[e].name != "M")

    def _zip_moe_ffn(self, lp, x, layer_idx: int, owners=None):
        """x: [B, 1, d].  Router -> engine fetch -> grouped expert FFN.

        ``owners`` (continuous batching) maps batch rows to request ids:
        the selection UNION across rows feeds one Algorithm-1 submission,
        while per-request accounting runs on pure residency queries."""
        cfg = self.cfg
        ffn = lp["ffn"]
        with span("zipmoe.route", layer=layer_idx):
            top_p, top_i, _ = route(ffn["router"], x, cfg)   # [B,1,k]
            ids = sorted({int(e) for e in np.asarray(top_i).reshape(-1)})
        B = x.shape[0]
        self._last_ids[layer_idx] = ids
        if owners is not None:
            with span("zipmoe.req_accounting", layer=layer_idx):
                self._note_request_access(layer_idx, top_i, owners)
        # expert-weight transfer attributed to this layer-step (background
        # reconstruction charges the step it lands in — approximate but
        # exact in the two cases that matter: 0 on a full cache hit, and
        # the whole re-upload on a host-mode hit)
        h2d0 = self.engine.h2d_bytes
        splice0 = self.engine.splice_s
        wcopy0 = self.engine.w_copy_bytes
        if self.prefetch:
            # overlap the next MoE layer's reconstruction with this layer's
            # FFN and the following layers' attention compute
            self._issue_prefetch(self._next_moe_layer(layer_idx), B)
        t0 = time.perf_counter()
        # consumes the pending step job and submits this layer's next one:
        # the next-step prediction rides behind any misprediction demand
        # under one Algorithm-1 block schedule, getting a full decode step
        # of compute to hide under
        try:
            weights, io_bytes, blocked_s = self._acquire_experts(
                layer_idx, ids, B)
        except (FetchError, FetchTimeout) as exc:
            # map the failed experts through the router's selection to the
            # batch rows that needed them — the server retires ONLY those
            # rows.  A timeout names no experts: the whole step is suspect.
            failed = {e for (l, e) in getattr(exc, "failures", {})
                      if l == layer_idx} or set(ids)
            ti = np.asarray(top_i).reshape(B, -1)
            rows = [b for b in range(B)
                    if {int(v) for v in ti[b]} & failed]
            raise StepFault(layer_idx, failed, rows or range(B), exc) \
                from exc
        fetch_s = time.perf_counter() - t0
        t_ffn = time.perf_counter()
        if self.fused_recovery:
            y = (self._ffn_zip_loop if self.ffn_impl == "loop"
                 else self._ffn_zip_gemm)(x, top_p, top_i, weights, ids)
        elif self.ffn_impl == "loop":
            y = self._ffn_loop(x, top_p, top_i, weights)
        elif self.ffn_impl == "grouped":
            y = self._ffn_grouped(x, top_p, top_i, weights, ids)
        else:
            y = self._ffn_ragged(x, top_p, top_i, weights, ids)
        if self.profile_p_times:
            # refine the measured bucket with the *actual* expert FFN wall
            # time (EMA) — forcing the value here keeps the observation
            # honest at the cost of one early sync per MoE layer.  Only
            # already-measured buckets are refined: a first observation of a
            # fresh bucket would bake the grouped-GEMM jit compile time into
            # p (measure()'s warmup run eats it), and observed-only buckets
            # the scheduler never reads would pile up as dead entries.
            cols = max(1, B * cfg.top_k)
            if self.profiler.has(layer_idx, len(ids), cols):
                y = jax.block_until_ready(y)
                self.profiler.record(layer_idx, len(ids), cols,
                                     time.perf_counter() - t_ffn)
        if "shared" in ffn:
            with span("zipmoe.shared_ffn", layer=layer_idx):
                y = y + apply_mlp(ffn["shared"], x, cfg)
        self.stats.append({"layer": layer_idx, "fetch_s": fetch_s,
                           "blocked_s": blocked_s, "io_bytes": io_bytes,
                           "n_experts": len(ids),
                           "h2d_bytes": self.engine.h2d_bytes - h2d0,
                           "w_copy_bytes": self.engine.w_copy_bytes - wcopy0,
                           "splice_s": self.engine.splice_s - splice0})
        return y

    def decode_step(self, tokens: jnp.ndarray, caches: list, pos: int
                    ) -> Tuple[jnp.ndarray, list]:  # hot-path
        """tokens: [B, 1] -> (logits [B,1,V], caches)."""
        with span("zipmoe.step"):
            return self._forward(tokens, caches, jnp.int32(pos), None)

    def decode_rows(self, tokens: jnp.ndarray, caches: list, positions,
                    owners=None) -> Tuple[jnp.ndarray, list]:  # hot-path
        """Multi-request decode step (continuous batching): each batch row
        is an independent request at its own sequence position.

        tokens: [B, 1]; caches: per-layer views from ``KVPagePool.gather``;
        positions: int32 [B] (row b's new-token index); owners: optional
        per-row request ids for per-request cache accounting.  Rows share
        ONE forward pass — every MoE layer submits a single Algorithm-1
        block list over the union of all rows' demand + predicted experts,
        so the cache pools, device slabs, and live planner serve the whole
        active set as shared multi-tenant resources.  Returns
        (logits [B, 1, V], updated caches).
        """
        with span("zipmoe.step"):
            lg, new_caches = self._forward(
                tokens, caches, jnp.asarray(positions, jnp.int32), owners)
            for rid in owners or ():
                self.req_stats.setdefault(
                    rid, {"accesses": 0, "hits": 0, "steps": 0})["steps"] += 1
            return lg, new_caches

    def _forward(self, tokens, caches: list, pos, owners
                 ) -> Tuple[jnp.ndarray, list]:  # hot-path
        """The layer loop of one decode step.  ``pos`` is a scalar position
        (``decode_step``: every row at the same one) or int32 [B] (rows at
        their own, ``decode_rows``); ``owners`` as in ``decode_rows``."""
        cfg = self.cfg
        p = self.globals
        rows = pos.ndim == 1
        x = p["embed"]["tok"][tokens]
        if cfg.pos == "learned":
            x = x + (p["embed"]["pos"][pos][:, None] if rows
                     else p["embed"]["pos"][pos][None, None])
        if cfg.attn == "mla":
            attend = attn_lib.mla_decode_rows if rows else attn_lib.mla_decode
        else:
            attend = attn_lib.gqa_decode_rows if rows else attn_lib.gqa_decode
        new_caches = []
        # loop-ok: per-LAYER structure (hot-path bans per-EXPERT loops;
        # expert work inside goes through the grouped-GEMM path)
        for idx, (lp, cache) in enumerate(zip(self.layers, caches)):
            with span("zipmoe.attn", layer=idx):
                h = apply_norm(lp["norm1"], x, cfg)
                if "attn" in lp:
                    y, kv = attend(lp["attn"], h, cfg, cache["kv"], pos)
                    nc = {"kv": kv}
                else:
                    y, sc = mamba_lib.mamba_decode(lp["mamba"], h, cfg,
                                                   cache["ssm"])
                    nc = {"ssm": sc}
                x = x + y
            if "ffn" in lp:
                h2 = apply_norm(lp["norm2"], x, cfg)
                if "router" in lp["ffn"]:
                    x = x + self._zip_moe_ffn(lp, h2, idx, owners=owners)
                else:
                    with span("zipmoe.dense_ffn", layer=idx):
                        x = x + apply_mlp(lp["ffn"], h2, cfg)
            new_caches.append(nc)
        with span("zipmoe.lm_head"):
            x = apply_norm(p["final_norm"], x, cfg)
            w = p["embed"]["tok"].T if cfg.tie_embeddings \
                else p["lm_head"]["w"]
            lg = x @ w
        if self._auto_depth:
            self._tune_depth()
        self.engine.note_step()       # windowed cache telemetry step clock
        return lg, new_caches

    def drain_pending(self) -> int:
        """Finish every in-flight prediction job and credit its stats —
        called when requests retire ahead of their predictions' tails (or
        at end of serving) so the cache pools' byte accounting and the
        overlap telemetry are stable with no job left half-collected.
        Blocks until the jobs complete; returns the drained io_bytes."""
        ov = self.overlap_stats
        io = 0
        for layer in list(self._pending):
            with span("zipmoe.fetch.drain", layer=layer):
                for h, _ in self._pending[layer]:
                    try:
                        _, st = h.spec_result()
                    except FetchTimeout:
                        # a hung speculative job must not wedge shutdown:
                        # drop the handle (the deadline hit is already
                        # counted by the engine) and keep draining the rest
                        continue
                    if not getattr(h, "_drained_stats", False):
                        h._drained_stats = True
                        ov["fetch_wall_s"] += st.wall
                        io += st.io_bytes
            self._pending[layer] = []
        return io

    def request_summary(self) -> Dict[int, Dict[str, float]]:
        """Per-request cache accounting (continuous batching): expert
        accesses, hits at step start, hit rate, and decode steps served —
        the fairness complement to the shared-pool :meth:`cache_summary`."""
        out = {}
        for rid, st in sorted(self.req_stats.items()):
            acc = st["accesses"]
            out[rid] = {"accesses": acc, "hits": st["hits"],
                        "hit_rate": st["hits"] / acc if acc else 0.0,
                        "steps": st["steps"]}
        return out

    # ------------------------------------------------------------------
    def generate(self, prompt_last_token: jnp.ndarray, caches, start_pos: int,
                 max_new_tokens: int = 16):
        """Greedy decode loop from an existing cache state."""
        tok = prompt_last_token
        out = []
        t_steps = []
        for i in range(max_new_tokens):
            t0 = time.perf_counter()
            logits, caches = self.decode_step(tok, caches, start_pos + i)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            t_steps.append(time.perf_counter() - t0)
            out.append(np.asarray(tok))
        return np.concatenate(out, axis=1), caches, {
            "tpot_s": float(np.mean(t_steps)), "steps_s": t_steps,
            "overlap": self.overlap_summary()}
