"""Serving driver: batched requests through the ZipMoE engine or resident
params.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-moe-a2.7b \\
      --mode zipmoe --requests 8 --max-new 16

Model size: without ``--layers`` the arch runs as a small smoke preset
(d_model 256, 6 layers, vocab 2048) that any CPU serves in seconds.
``--layers N`` serves the registered config at its published widths with
the depth cut to N layers (random weights from seed 0), e.g.
qwen2-moe-a2.7b at ``--layers 4`` on one TPU v5e.

Compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout (:func:`enable_compile_cache`).

--mode resident     : standard in-memory serving (BatchServer)
--mode zipmoe       : routed experts live ONLY in the compressed store; every
                      MoE layer fetches through cache pools + the Alg-1
                      scheduler, with overlapped prefetch (--no-prefetch to
                      compare against the synchronous path).
--mode zipmoe-batch : continuous batching (BatchServer) over the compressed
                      store end-to-end — requests admit/retire between decode
                      steps into a shared KV page pool, one Algorithm-1 block
                      list per step over the union of active requests.
                      --max-concurrency N caps the active set, --arrival-trace
                      replays offsets (e.g. ``0,0.05,0.1``), --static-batch
                      falls back to the legacy epoch discipline (the
                      baseline the benchmarks compare against).  Prints
                      TTFT/TPOT/queue-delay percentiles plus the
                      per-request fairness table.

Cache knobs (§3.4):
--mem-budget BYTES   : byte-budgeted live pool planning — ONE global byte
                       budget for all layers' pools; per-layer F/C/S/E
                       splits are solved online by the §3.4 planner from
                       live activation ranks and re-planned under drift
                       (--replan-every N steps, --plan-step grid).  The
                       primary sizing interface; --pool-sizes becomes a
                       static override.
--pool-sizes F,C,S,E : hierarchical pool capacities (experts per layer),
                       e.g. ``--pool-sizes 2,2,4,8``.  Without
                       --mem-budget this is the static default; with it,
                       explicit pool sizes seed the capacities until the
                       first drift re-plan.
--cache-mode flat    : flat full-tensor baseline instead of the F≺C≺S≺E
                       hierarchy (--flat-policy lru|fifo|lfu|marking,
                       --flat-capacity N; default N = sum of pool sizes)
--delta              : δ rank-tolerance margin of the dispatch thresholds
--device-cache       : device-resident expert slabs — the F tier lives on
                       the accelerator, a demand miss splice-admits into a
                       slab slot in one aliased kernel launch, and the
                       ragged FFN reads the slab in place by slot index
                       (zero host→device weight bytes AND zero weight-copy
                       bytes on a cache-hit step)
--ffn-impl           : ragged (slot-indexed megakernel, default) | grouped
                       (padded [Ea, C, d] batch) | loop (reference)

Scheduler knobs (§3.3):
--profile-p-times    : feed Algorithm 1 *measured* per-expert grouped-GEMM
                       times (GemmProfiler) instead of class constants
--cross-layer-depth N: one block schedule spans this step plus the next N
                       MoE layers' predictions (``auto`` tunes N online
                       from the observed hidden-fetch fraction)
--freq-decay         : FreqTracker forgetting for drifted workloads
--cache-window N     : windowed (per-N-steps) cache hit-rate series

Failure model (DESIGN.md §Failure model):
--fault-plan SPEC    : seeded deterministic fault injection, e.g.
                       ``bitflip:p=0.1;eio:count=3;worker_kill:count=1;
                       seed=42`` — corrupted reads are caught by per-chunk
                       checksums and retried, killed workers are respawned
                       by the watchdog, failed requests retire with an
                       error while survivors decode on.  Prints the
                       ``faults:`` telemetry line (injected firings,
                       retries, quarantines, worker restarts).
--no-verify          : skip per-chunk checksum verification on read

Peer-HBM tier (tier stack P):
--mesh N             : shard store + slabs over N devices ('ep'); demand
                       misses resident in a neighbor device's slab fetch
                       over the interconnect (collective_permute) instead
                       of the host decode path
--budget-split       : proportional | waterfill (marginal-gain budget
                       allocation across layers)
--peer-budget BYTES  : per-device peer-slab budget (default --mem-budget)

Both modes print ``cache:`` telemetry (per-pool hit rates, residency-state
transition counts) next to the ``overlap:`` line.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.faults import FaultPlan
from repro.core.store import build_store, drop_routed_experts
from repro.models import init_params
from repro.serving.server import BatchServer
from repro.serving.zipserve import ZipServer

# the checkout: src/repro/launch/serve.py -> three levels up from src/
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
    it itself, so nothing else is set), else ``.jax_cache/`` in the
    checkout — a fixed path, since the path is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str, layers=None):
    """The served config: the smoke preset without ``layers``, else the
    registered config at its published widths cut to ``layers`` layers."""
    if layers is None:
        return get_smoke_config(arch, d_model=256, n_layers=6,
                                vocab_size=2048)
    cfg = get_config(arch)
    if not 1 <= layers <= cfg.n_layers:
        raise ValueError(f"--layers must be in [1, {cfg.n_layers}] for "
                         f"{arch}, got {layers}")
    return dataclasses.replace(cfg, n_layers=layers)


def init_model(cfg, seed: int):
    """Seeded random weights, built by one compiled program (no eager
    per-layer temporaries at published widths)."""
    return jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(seed),
                                                  cfg)


def build_zipmoe_server(params, cfg, store_dir: str, *, max_batch: int,
                        max_len: int, max_concurrency=None,
                        continuous: bool = True, **zip_kw) -> BatchServer:
    """The served ZipMoE stack: build the compressed store from ``params``,
    release the device buffers of the routed experts (the store is now
    their only copy, so ``params`` no longer serves the resident path), and
    put a :class:`BatchServer` over a :class:`ZipServer` on it.  ``zip_kw``
    goes to ZipServer; the returned server's ZipServer is ``.zip``."""
    t0 = time.perf_counter()
    store = build_store(params, cfg, store_dir)
    print(f"store: {store_dir} ratio={store.ratio():.3f} "
          f"rho={store.rho():.3f} build_s={time.perf_counter() - t0:.1f}")
    zs = ZipServer(drop_routed_experts(params), cfg, store_dir, **zip_kw)
    return BatchServer(None, cfg, max_batch=max_batch, max_len=max_len,
                       zip_server=zs, max_concurrency=max_concurrency,
                       continuous=continuous)


def print_sched_telemetry(zs, args):
    """Windowed cache series + measured p-time buckets (both ZipMoE modes)."""
    if args.cache_window:
        ws = zs.cache_summary(windows=True)["windows"]
        print("cache windows (hit rate per",
              f"{args.cache_window}-step window):",
              " ".join(f"{w['step_end']}:{w['hit_rate']:.2f}" for w in ws))
    if args.profile_p_times:
        ps = zs.p_time_summary()
        print(f"p-times: {ps['n_buckets']} buckets, "
              f"{ps['n_measurements']} measured "
              f"({ps['measure_wall_s']*1e3:.1f}ms profiling)")
    if args.mem_budget is not None:
        pls = zs.plan_summary()
        order = zs.engine.stack.order      # F/C/S/E, plus P on a mesh
        sizes = {l: "".join(f"{p}{s[p]}" for p in order if p in s)
                 for l, s in sorted((int(l), d["sizes"])
                                    for l, d in pls["layers"].items())}
        print(f"plan: budget={pls['mem_budget']:.0f}B "
              f"resident={pls['bytes_resident']:.0f}B "
              f"replans={pls['n_replans']} "
              f"({', '.join(ev['reason'] for ev in pls['replans'])}) "
              f"sizes={sizes}")
    if args.mesh > 1:
        ps = zs.peer_summary()
        print(f"peer: served={ps['served']} fallbacks={ps['fallbacks']} "
              f"collective_bytes={ps['total_bytes']} "
              f"put_bytes={ps['peer_put_bytes']} "
              f"link_bw={ps['link']['bw']/1e9:.1f}GB/s")
    if zs._auto_depth:
        ov = zs.overlap_summary()
        print(f"auto-depth: depth={ov['cross_layer_depth']} "
              f"changes={len(ov['depth_events'])}")
    fs = zs.fault_summary()
    if args.fault_plan or fs["failed_experts"] or fs["worker_restarts"]:
        st = fs["store"]
        print(f"faults: injected={fs.get('injected', {}).get('total', 0)} "
              f"retries={st['read_retries']} "
              f"checksum_failures={st['checksum_failures']} "
              f"quarantined={st['quarantined']} "
              f"worker_restarts={fs['worker_restarts']} "
              f"deadline_hits={fs['deadline_hits']} "
              f"spec_drops={fs['spec_drops']} "
              f"fallback_loads={fs['fallback_loads']} "
              f"failed_experts={fs['failed_experts']} "
              f"refetches={fs['fault_refetches']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve the arch at its published widths with the "
                         "depth cut to N layers (default: the small smoke "
                         "preset)")
    ap.add_argument("--mode", default="zipmoe",
                    choices=["resident", "zipmoe", "zipmoe-batch"])
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable overlapped expert prefetch")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-concurrency", type=int, default=None,
                    help="continuous batching: max requests decoding at "
                         "once (admit/retire between steps; default "
                         "--batch)")
    ap.add_argument("--arrival-trace", default=None,
                    help="comma-separated arrival offsets in seconds, one "
                         "per request (cycled), replayed from serve start; "
                         "e.g. ``0,0.05,0.1``")
    ap.add_argument("--static-batch", action="store_true",
                    help="zipmoe-batch: use the legacy epoch discipline "
                         "(bucket, prefill together, decode in lockstep) "
                         "instead of continuous batching")
    ap.add_argument("--store-dir", default=None)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--bandwidth-gbps", type=float, default=None,
                    help="emulate a slow offload tier")
    ap.add_argument("--pool-sizes", default=None,
                    help="hierarchical pool capacities F,C,S,E per layer "
                         "(default 2,2,4,8; with --mem-budget: a static "
                         "override of the initial plan)")
    ap.add_argument("--mem-budget", type=float, default=None,
                    help="global cache byte budget: per-layer pools are "
                         "planned online (§3.4) and re-planned under "
                         "drift instead of using fixed --pool-sizes")
    ap.add_argument("--replan-every", type=int, default=16,
                    help="probe the windowed hit rate every N decode steps "
                         "and re-plan the pools on drift (--mem-budget)")
    ap.add_argument("--plan-step", type=float, default=0.25,
                    help="γ grid resolution of the §3.4 pool-ratio search")
    ap.add_argument("--cache-mode", default="hier", choices=["hier", "flat"],
                    help="hierarchical F/C/S/E pools vs flat full-tensor map")
    ap.add_argument("--flat-policy", default="lru",
                    choices=["lru", "fifo", "lfu", "marking"])
    ap.add_argument("--flat-capacity", type=int, default=None,
                    help="flat-mode capacity (default: sum of pool sizes)")
    ap.add_argument("--delta", type=int, default=1,
                    help="dispatch-threshold rank tolerance δ")
    ap.add_argument("--device-cache", action="store_true",
                    help="device-resident expert slabs: fused splice-admit "
                         "on device, F pool holds slab slots, the ragged "
                         "FFN reads the slab in place by slot index (no "
                         "per-step weight copy, no host re-upload)")
    ap.add_argument("--ffn-impl", default="ragged",
                    choices=["ragged", "grouped", "loop"],
                    help="expert FFN path: slot-indexed ragged megakernel "
                         "(default), padded grouped GEMM, or the per-token "
                         "reference loop")
    ap.add_argument("--profile-p-times", action="store_true",
                    help="sort Algorithm-1 blocks by measured per-expert "
                         "grouped-GEMM times instead of class constants")
    ap.add_argument("--cross-layer-depth", default="0",
                    help="extend each step submission with the next N MoE "
                         "layers' predictions under one block schedule; "
                         "'auto' tunes N online from the observed "
                         "hidden-fetch fraction")
    ap.add_argument("--mesh", type=int, default=1,
                    help="shard the compressed store and expert slabs over "
                         "N devices ('ep' axis) and add the peer-HBM (P) "
                         "tier: demand misses resident in a neighbor's "
                         "slab fetch via collective_permute instead of the "
                         "host decode path (CPU: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--budget-split", default="proportional",
                    choices=["proportional", "waterfill"],
                    help="cross-layer byte-budget split: activity-"
                         "proportional, or water-filling on marginal "
                         "makespan gain per byte")
    ap.add_argument("--peer-budget", type=float, default=None,
                    help="per-device peer-slab byte budget (default: "
                         "--mem-budget)")
    ap.add_argument("--freq-decay", type=float, default=1.0,
                    help="FreqTracker exponential decay (<1 forgets stale "
                         "popularity under drifting traces; 1.0 = never)")
    ap.add_argument("--cache-window", type=int, default=0,
                    help="record cache hit/miss deltas every N decode steps "
                         "(cache_summary windowed series; 0 = off)")
    ap.add_argument("--fault-plan", default=None,
                    help="seeded fault injection spec, e.g. "
                         "'bitflip:p=0.1;eio:count=3;worker_kill:count=1;"
                         "seed=42' (kinds: bitflip, truncate, eio, delay, "
                         "worker_kill, peer_link)")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip per-chunk checksum verification on read")
    ap.add_argument("--fetch-deadline", type=float, default=120.0,
                    help="seconds before a blocked expert fetch raises "
                         "FetchTimeout instead of hanging (0 = unbounded)")
    args = ap.parse_args()
    if args.cross_layer_depth != "auto":
        try:
            args.cross_layer_depth = int(args.cross_layer_depth)
        except ValueError:
            ap.error("--cross-layer-depth expects an integer or 'auto'")
    pool_sizes = None
    if args.pool_sizes is None:
        if args.mem_budget is None:
            args.pool_sizes = "2,2,4,8"     # static default, no planner
    if args.pool_sizes is not None:
        parts = args.pool_sizes.split(",")
        try:
            pool_sizes = dict(zip("FCSE", (int(x) for x in parts)))
        except ValueError:
            pool_sizes = None
        if pool_sizes is None or len(parts) != 4:
            ap.error("--pool-sizes expects exactly 4 comma-separated "
                     "integers (F,C,S,E), e.g. 2,2,4,8")

    enable_compile_cache()
    try:
        cfg = model_config(args.arch, args.layers)
    except ValueError as e:
        ap.error(str(e))
    if args.layers is not None:
        print(f"config: {cfg.name} at published widths, depth cut to "
              f"{cfg.n_layers} of {get_config(args.arch).n_layers} layers")
    params = init_model(cfg, 0)
    rng = np.random.default_rng(0)

    if args.mode == "resident":
        srv = BatchServer(params, cfg, max_batch=args.batch)
        for _ in range(args.requests):
            srv.submit(rng.integers(0, cfg.vocab_size, args.prompt_len),
                       args.max_new)
        srv.run()
        print("metrics:", srv.metrics())
        return

    # ---- ZipMoE mode -------------------------------------------------------
    store_dir = args.store_dir or tempfile.mkdtemp(prefix="zipmoe_store_")
    srv = build_zipmoe_server(
        params, cfg, store_dir, max_batch=args.batch,
        max_len=args.prompt_len + args.max_new,
        max_concurrency=args.max_concurrency,
        continuous=not args.static_batch,
        L=args.workers, pool_sizes=pool_sizes,
        bandwidth_gbps=args.bandwidth_gbps,
        prefetch=not args.no_prefetch, ffn_impl=args.ffn_impl,
        cache_mode=args.cache_mode, flat_capacity=args.flat_capacity,
        flat_policy=args.flat_policy, delta=args.delta,
        profile_p_times=args.profile_p_times,
        cross_layer_depth=args.cross_layer_depth,
        freq_decay=args.freq_decay, cache_window=args.cache_window,
        device_cache=args.device_cache, mem_budget=args.mem_budget,
        replan_every=args.replan_every, plan_step=args.plan_step,
        budget_split=args.budget_split, mesh_devices=args.mesh,
        peer_budget=args.peer_budget,
        verify=False if args.no_verify else None,
        faults=(FaultPlan.parse(args.fault_plan)
                if args.fault_plan else None),
        fetch_deadline_s=args.fetch_deadline or None)
    del params                        # routed experts now live in the store
    zs = srv.zip

    if args.mode == "zipmoe-batch":
        arrivals = ([float(x) for x in args.arrival_trace.split(",")]
                    if args.arrival_trace else [0.0])
        for i in range(args.requests):
            srv.submit(rng.integers(0, cfg.vocab_size, args.prompt_len),
                       args.max_new, arrival_s=arrivals[i % len(arrivals)])
        srv.run()
        print("metrics:", srv.metrics())
        for rid, d in sorted(srv.request_summary().items()):
            parts = []
            if d.get("error"):
                parts.append(f"FAILED ({d['error']})")
            if d["ttft_s"] is not None:
                parts.append(f"ttft={d['ttft_s']*1e3:.1f}ms")
            if d["tpot_s"] is not None:
                parts.append(f"tpot={d['tpot_s']*1e3:.1f}ms")
            if d["queue_delay_s"] is not None:
                parts.append(f"qdelay={d['queue_delay_s']*1e3:.1f}ms")
            if "cache_hit_rate" in d:
                parts.append(f"hit_rate={d['cache_hit_rate']:.2f}")
            print(f"request[{rid}]: toks={d['n_tokens']}", " ".join(parts))
        print("cache:", srv.cache_summary())
        print_sched_telemetry(zs, args)
        zs.close()
        n_failed = sum(1 for r in srv.finished if r.error)
        if n_failed and not args.fault_plan:
            # a failed request outside fault injection is a fault of the
            # serving path (a kernel or fetch error), not an outcome
            sys.exit(f"error: {n_failed} request(s) failed")
        return

    B = args.batch
    S = args.prompt_len
    caches = zs.init_cache(B, S + args.max_new)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    t0 = time.time()
    out, caches, m = zs.generate(tok, caches, S, max_new_tokens=args.max_new)
    print(f"generated {out.shape} in {time.time()-t0:.2f}s "
          f"tpot={m['tpot_s']*1e3:.1f}ms")
    io = sum(s["io_bytes"] for s in zs.stats)
    print(f"expert I/O total={io/1e6:.2f}MB over {len(zs.stats)} layer-fetches")
    cs = zs.cache_summary()
    print(f"cache[{cs['mode']}]: hits by state:", cs["hits"],
          f"misses: {cs['misses']} hit_rate={cs['hit_rate']:.2f}")
    print("cache transitions:", cs["transitions"],
          f"evictions={cs['evictions']} occupancy={cs['occupancy']}")
    ov = zs.overlap_summary()
    print(f"overlap: hidden={ov['hidden_fetch_s']*1e3:.1f}ms of "
          f"{ov['total_fetch_s']*1e3:.1f}ms fetch "
          f"(frac={ov['hidden_frac']:.2f}, pred_hits={ov['pred_hits']} "
          f"misses={ov['pred_misses']})")
    n_steps = max(1, args.max_new)
    print(f"transfer: h2d={ov['h2d_bytes']/1e6:.2f}MB "
          f"({ov['h2d_bytes']/n_steps/1e3:.1f}kB/step) "
          f"w_copy={ov['w_copy_bytes']/1e6:.2f}MB "
          f"splice={ov['splice_ms']:.1f}ms/{ov['splice_ops']}ops "
          f"slab_writes={ov['slab_writes']} "
          f"slab_resident={ov['slab_resident']}")
    print(f"gemm: pad_frac={ov['pad_frac']:.3f} "
          f"(real={ov['tokens_real']} padded={ov['tokens_padded']} rows)")
    print_sched_telemetry(zs, args)
    zs.close()


if __name__ == "__main__":
    main()
