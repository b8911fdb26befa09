"""One run of one cell: set-up, ramp, window, check.

Set-up makes the weights on the device from ``--seed`` (one jitted call),
builds the compressed expert store from them, and starts ``BatchServer``
over ``ZipServer(device_cache=True, ffn_impl="ragged")`` as
``repro.launch.serve.build_zipmoe_server`` does.  It then runs one decode
step at every KV view length the traffic can reach and the expert FFN at
every count of selected experts, so nothing compiles in the window, and
serves the ramp: each client's first request, staggered.
The window opens when every client has retired its ramp request and closes
``seconds`` later; requests still in flight are cut, not drained.

The benchmark wraps ``ZipServer.decode_rows`` on the instance: the wrapper
blocks on the logits and timestamps each step, from which ``window.py``
computes the end-to-end metrics.  Host spans (``bench.*``) mark the calls
the benchmark can reach from outside for the trace's idle attribution.

After the window the served tokens of a seeded sample of the requests,
the longest among them, are compared with the configuration's plain
float32 reference (``check.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, spec, traffic, weights
from bench import trace as trace_lib
from bench import window as win

CACHE = os.path.join(spec.BENCH_DIR, ".cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(Exception):
    """The chips the cell asks for are not there, or not in the peak table."""


class WindowClosed(Exception):
    """Raised out of ``BatchServer.run()`` when the window has closed."""


@dataclass
class Run:
    """What the per-layer readers see of one run's window."""
    steps: List[win.Step]
    n_steps: int
    c0: dict
    c1: dict
    layer_steps: List[dict]
    compiles: int
    trace: Optional[dict]
    peaks: dict
    conf: dict
    d_model: int
    d_expert: int
    tokens_per_s: float
    mean_ctx: float


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is on the "
                       f"{d.platform!r} platform")
    if len(devs) < chips:
        raise NoDevice(f"{chips} chips wanted, {len(devs)} visible")
    peaks = spec.load_peaks()
    if require_tpu and d.device_kind not in peaks:
        raise NoDevice(f"device kind {d.device_kind!r} is not in "
                       f"bench/peaks.json")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs),
            "peaks": peaks.get(d.device_kind)}


# the program's options: fields of its config that a configuration sets
# rather than checks
OPTIONS = ("router_norm_topk",)


def program_config(conf: dict, ref=None):
    """The program's config for ``conf``: the registered arch at the
    configuration's depth, with the options among the fields that the
    configuration's reference module fixes (``program_fields``) set and
    the other fields checked, and every layer's kind checked against the
    reference's tensors for that layer."""
    from repro.launch import serve as serve_lib
    ref = ref or spec.reference_module(conf)
    want = dict(ref.program_fields(conf))
    cfg = serve_lib.model_config(conf["arch"], conf["num_hidden_layers"])
    cfg = dataclasses.replace(cfg, **{k: want[k] for k in OPTIONS if k in want})
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config differs from {conf['arch']}: "
                         f"{got} != {want}")
    _check_layers(cfg, ref.leaves(conf))
    return cfg


def _check_layers(cfg, leaves) -> None:
    """Each layer's mixer (``attn/`` or ``mamba/`` tensors), whether it
    routes (``ffn/router``) and whether the head is tied (no
    ``lm_head/w``), as the reference's tensors say, against the program."""
    from repro.models import transformer
    glob, layers = leaves
    if len(layers) != cfg.n_layers:
        raise ValueError(f"the reference has {len(layers)} layers, the "
                         f"program {cfg.n_layers}")
    tied = "lm_head/w" not in glob
    if tied != cfg.tie_embeddings:
        raise ValueError(f"the reference's head tied: {tied}; the "
                         f"program's tie_embeddings: {cfg.tie_embeddings}")
    for i, names in enumerate(layers):
        mixer = ("attn" if any(n.startswith("attn/") for n in names) else
                 "mamba" if any(n.startswith("mamba/") for n in names)
                 else None)
        theirs = (mixer, "ffn/router" in names)
        mine = (transformer.mixer_kind(cfg, i), cfg.moe_layer(i))
        if mine != theirs:
            raise ValueError(f"layer {i}: the program has (mixer, routed) "
                             f"{mine}, the reference {theirs}")


def _counters(zs) -> dict:
    cs = zs.cache_summary()
    return {"overlap": dict(zs.overlap_stats),
            "hits_F": cs["hits"].get("F", 0), "accesses": cs["accesses"],
            "splice_admits": (zs.engine.transfer_summary()["splice_ops"]
                              - zs.engine.splice_ops),
            "splices": zs.engine.splice_ops,
            "stats_len": len(zs.stats)}


def _wrap(obj, name: str, span: str):
    """Wrap ``obj.name`` on the instance in a host span."""
    from jax.profiler import TraceAnnotation
    inner = getattr(obj, name)

    def wrapped(*a, **k):
        with TraceAnnotation(span):
            return inner(*a, **k)

    setattr(obj, name, wrapped)


@dataclass
class _State:
    t_start: Optional[float] = None
    t_end: Optional[float] = None
    t_closed: Optional[float] = None
    k0: int = 0
    k1: int = 0
    c0: dict = field(default_factory=dict)
    c1: dict = field(default_factory=dict)
    span: object = None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_proc: float, require_tpu: bool = True,
             tamper: Optional[Callable] = None, log=print,
             dump_events: Optional[str] = None, control: bool = False,
             cache: str = CACHE) -> dict:
    """One run; returns the result line's object (without printing it).
    The store and the trace are written under ``cache``."""
    import jax
    from jax import monitoring
    from repro.launch import serve as serve_lib

    dev = device_info(cell.chips, require_tpu)
    peaks = dev.pop("peaks")
    serve_lib.enable_compile_cache()
    # every program, eager operations too, goes into the persistent cache,
    # so a second run of a cell compiles nothing in its set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles: List[float] = []

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    monitoring.register_event_duration_secs_listener(on_duration)
    ref = spec.reference_module(cell.config)
    pcfg = program_config(cell.config, ref)
    tr = cell.traffic
    parts: Dict[str, float] = {}
    with _fresh_dir(os.path.join(cache, "store", cell.config_name)) as store:
        zs, srv = _build_server(cell, seed, pcfg, ref, store, parts, log)
        try:
            t = time.perf_counter()
            stream = traffic.Stream(tr, pcfg.vocab_size, seed)
            _warm_shapes(srv, zs, int(tr["clients"]), stream.max_alloc(),
                         pcfg.vocab_size, seed)
            parts["warmup_s"] = time.perf_counter() - t
            t = time.perf_counter()
            rec = _serve(srv, zs, stream,
                         os.path.join(cache, "trace", cell.name), seconds,
                         trace, tamper)
            st = rec["st"]
            parts["ramp_s"] = st.t_start - t
            stats = jax.devices()[0].memory_stats() or {}
            dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        finally:
            zs.close()
        setup_s = st.t_start - t_proc
        log("setup: " + " ".join(f"{k}={v:.3f}" for k, v in parts.items())
            + f" total_s={setup_s:.3f}")
        w_steps = rec["steps"][st.k0:st.k1]
        in_window = sum(st.t_start <= c <= st.t_closed for c in compiles)
        layer_steps = list(zs.stats[st.c0["stats_len"]:st.c1["stats_len"]])
        del srv, zs
        gc.collect()
        ex = None
        if trace:
            ex = trace_lib.extract(rec["trace_dir"])
            if dump_events:
                with open(dump_events, "w") as f:
                    json.dump(trace_lib.trim(ex, 1.0), f,
                              separators=(",", ":"))
            shutil.rmtree(rec["trace_dir"], ignore_errors=True)
        t = time.perf_counter()
        checks = check.compare(ref, cell.config, seed, rec["requests"],
                               rec["prompts"], cell.cell.get("limits", {}),
                               int(tr["max_len"]), control=control)
        log(f"check: {time.perf_counter() - t:.3f}s, "
            f"{checks['compared_tokens']} served tokens of "
            f"{checks['compared_requests']} requests; served "
            f"logit_gap_max={checks['served_gap_max']!r}"
            + ("; the control's tokens are compared" if control else ""))

    acc = win.account(rec["steps"], rec["prompt_len"], st.t_start, st.t_end)
    log(f"window: seconds={st.t_end - st.t_start:.3f} steps={len(w_steps)} "
        f"tokens={acc['tokens']} itl_gaps={len(acc['itl_s'])} "
        f"compiles={in_window}")
    active = {rid for s in w_steps for rid in s.owners}
    attempted = len(active | {rid for rid, t0 in rec["sent"].items()
                              if st.t_start <= t0 <= st.t_end})
    failed = sum(1 for r in rec["requests"] if r.error and r.rid in active)
    out = {"correct": bool(checks["correct"] and failed == 0
                           and acc["tokens"] > 0),
           "attempted": attempted, "failed": failed,
           "metrics": {}, "device": dev}
    if trace:
        run = Run(steps=w_steps, n_steps=len(w_steps), c0=st.c0, c1=st.c1,
                  layer_steps=layer_steps, compiles=in_window, trace=None,
                  peaks=peaks or {}, conf=cell.config, d_model=pcfg.d_model,
                  d_expert=pcfg.d_expert, tokens_per_s=acc["tokens_per_s"],
                  mean_ctx=_mean_context(w_steps, rec["prompt_len"]))
        _per_layer(cell, run, ex, out, log)
    else:
        vals = {"tokens_per_s": acc["tokens_per_s"], "setup_s": setup_s}
        if acc["itl_s"]:
            vals["itl_p50_ms"] = 1e3 * win.pct(acc["itl_s"], 50)
        for m in cell.metrics(False):
            if m["name"] in vals:
                out["metrics"][m["name"]] = {"value": vals[m["name"]],
                                             "unit": m["unit"]}
    out["checks"] = checks["numbers"]
    return out


@contextlib.contextmanager
def _fresh_dir(path: str):
    """``path`` emptied for the run and removed after it, however it ends."""
    shutil.rmtree(path, ignore_errors=True)
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


def _build_server(cell, seed, pcfg, ref, store_dir, parts, log):
    """Weights from the seed (one jitted call), the compressed store built
    from them, and ``BatchServer`` over ``ZipServer`` on it."""
    import jax
    from repro.core.store import build_store, drop_routed_experts
    from repro.models.transformer import stack_layout
    from repro.serving.server import BatchServer
    from repro.serving.zipserve import ZipServer

    glob, layer_specs = ref.leaves(cell.config)
    t = time.perf_counter()
    prefix, period, _ = stack_layout(pcfg)
    params = weights.program_params(seed, glob, layer_specs, len(prefix),
                                    period)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    store = build_store(params, pcfg, store_dir)
    parts["store_s"] = time.perf_counter() - t
    log(f"store: ratio={store.ratio():.4f} rho={store.rho():.4f} "
        f"groups={len(store.groups)}")
    t = time.perf_counter()
    pools = cell.cell
    zs = ZipServer(drop_routed_experts(params), pcfg, store_dir,
                   L=int(pools["workers"]), pool_sizes=dict(pools["pools"]),
                   device_cache=True, ffn_impl="ragged")
    srv = BatchServer(None, pcfg, max_batch=int(cell.traffic["clients"]),
                      max_len=int(cell.traffic["max_len"]), zip_server=zs)
    parts["server_s"] = time.perf_counter() - t
    return zs, srv


def _mean_context(steps: List[win.Step], prompt_len: Dict[int, int]) -> float:
    """Mean number of positions attended when a token was emitted."""
    ctx = [p + 1 for s in steps for rid, p in zip(s.owners, s.positions)
           if p >= prompt_len[rid] - 1]
    return float(np.mean(ctx)) if ctx else 0.0


def _per_layer(cell: spec.Cell, run: Run, ex: dict, out: dict, log):
    """The cell's per-layer metrics from the run and its trace, with the
    device's busy and window seconds and the breakdown."""
    mods = {m["name"]: (m, spec.metric_module(m["name"]))
            for m in cell.metrics(True)}
    kernels = {n: mod.KERNEL for n, (_, mod) in mods.items()
               if hasattr(mod, "KERNEL")}
    run.trace = trace_lib.reduce(ex, kernels)
    for name, (m, mod) in mods.items():
        v = mod.read(run)
        if v is not None:
            out["metrics"][name] = {"value": float(v), "unit": m["unit"]}
    if run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
        log("kernels: " + " ".join(
            f"{k}={v:.6f}s/{run.trace['kernel_calls'][k]}calls"
            for k, v in run.trace["kernel_s"].items()))


def _warm_shapes(srv, zs, clients: int, max_alloc: int, vocab: int,
                 seed: int):
    """One decode step, gather and commit at every KV view length the
    traffic can reach (``page_size`` multiples up to the longest request's
    allocation), with the server's own page-pool shape.  Step ``i`` feeds
    ``i`` distinct tokens over the rows, so the steps also route to few and
    to many distinct experts.  Then the expert FFN at every count of
    selected experts (``_warm_ffn``), with the first step's input."""
    import jax.numpy as jnp
    pool = srv._make_pool()
    rng = np.random.default_rng(seed)
    rids = list(range(-clients, 0))
    n_views = pool.pages_for(max_alloc)
    seen = []
    ffn = zs._ffn_ragged

    def first_input(x, *a):
        seen.append(x)
        return ffn(x, *a)

    zs._ffn_ragged = first_input
    for i in range(max(n_views, clients)):
        pages = min(i, n_views - 1) + 1
        for r in rids:
            pool.alloc(r, pages * pool.page_size)
        views = pool.gather(rids)
        pos = np.full(clients, pages * pool.page_size - 1, np.int32)
        distinct = rng.integers(0, vocab, min(i, clients - 1) + 1)
        toks = jnp.asarray(distinct[np.arange(clients) % len(distinct)],
                           jnp.int32)[:, None]
        lg, views = zs.decode_rows(toks, views, pos)
        lg.block_until_ready()
        pool.commit(views, rids, pos)
        for r in rids:
            pool.free(r)
    del zs._ffn_ragged
    zs.drain_pending()
    _warm_ffn(zs, seen[0])


def _warm_ffn(zs, x):
    """The routed experts' FFN at every count of selected experts that a
    step of ``x``'s rows can reach, on both of ``ZipServer._slab_sources``'
    paths: the slab read in place (counts up to its capacity) and the stack
    of a mixed step (every count), whose stack and GEMM shapes follow the
    count.  Row ``b`` selects experts ``b*k .. b*k+k-1`` modulo the count;
    the weights are slots of the layers' slabs, read by the same path."""
    import jax
    from repro.core.slab import SlotRef
    cfg = zs.cfg
    rows, k = x.shape[0], cfg.top_k
    slabs = {}
    for slab in zs.engine._slabs.values():
        if slab is not None:
            slabs.setdefault((slab.capacity, tuple(sorted(slab.shapes.items()))),
                             slab)
    top_p = np.full((rows, k), 1.0 / k, np.float32)
    for slab in slabs.values():
        cap = slab.capacity
        ref = lambda e, name: SlotRef(slab, e % cap, slab.gen[e % cap], name)
        for n in range(k, min(cfg.n_experts, rows * k) + 1):
            ids = list(range(n))
            top_i = (np.arange(rows)[:, None] * k + np.arange(k)) % n
            in_place = {e: {t: ref(e, t) for t in slab.shapes} for e in ids}
            mixed = dict(in_place)
            mixed[0] = {t: r.read() for t, r in in_place[0].items()}
            for weights in ([in_place, mixed] if n <= cap else [mixed]):
                jax.block_until_ready(
                    zs._ffn_ragged(x, top_p, top_i, weights, ids))


def _serve(srv, zs, stream, trace_dir, seconds, trace, tamper) -> dict:
    """Ramp and window through ``srv.run()``; the window's records."""
    import jax
    from jax.profiler import TraceAnnotation
    st = _State()
    steps: List[win.Step] = []
    prompt_len: Dict[int, int] = {}
    prompts: Dict[int, np.ndarray] = {}
    sent: Dict[int, float] = {}
    client_of: Dict[int, int] = {}
    reqs: Dict[int, object] = {}
    ramp_left = set(range(stream.clients))

    def send(client: int, req: traffic.Request):
        rid = srv.submit(req.prompt, req.max_new)
        sent[rid] = time.perf_counter()
        reqs[rid] = srv.queue[-1]
        prompt_len[rid] = len(req.prompt)
        prompts[rid] = req.prompt
        client_of[rid] = client

    def on_retire(r):
        c = client_of[r.rid]
        ramp_left.discard(c)
        if st.t_start is None and not ramp_left:
            st.c0 = _counters(zs)
            st.k0 = len(steps)
            st.t_start = time.perf_counter()
            st.t_end = st.t_start + seconds
            print("bench: window open", file=sys.stderr, flush=True)
            if trace:     # a span made before the trace started stays empty
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                st.span = TraceAnnotation(trace_lib.WINDOW)
                st.span.__enter__()
        send(c, stream.next())

    decode = zs.decode_rows

    def decode_rows(tokens, views, positions, owners=None):
        t_call = time.perf_counter()
        if st.t_end is not None and t_call >= st.t_end:
            st.c1 = _counters(zs)
            st.k1 = len(steps)
            st.t_closed = t_call
            print("bench: window closed", file=sys.stderr, flush=True)
            if trace:
                st.span.__exit__(None, None, None)
            raise WindowClosed()
        with TraceAnnotation("bench.decode_rows"):
            lg, views = decode(tokens, views, positions, owners=owners)
            if tamper is not None:
                lg = tamper(lg, owners, positions, prompt_len)
            lg = jax.block_until_ready(lg)
        steps.append(win.Step(t_call, time.perf_counter(), list(owners),
                              np.asarray(positions).tolist()))
        return lg, views

    zs.decode_rows = decode_rows
    _wrap(zs, "_acquire_experts", "bench.acquire_experts")
    _wrap(zs, "_zip_moe_ffn", "bench.moe_ffn")
    make_pool = srv._make_pool

    def traced_pool():
        pool = make_pool()
        _wrap(pool, "gather", "bench.kv_gather")
        _wrap(pool, "commit", "bench.kv_commit")
        return pool

    srv._make_pool = traced_pool
    srv.on_retire = on_retire
    for c, req in enumerate(stream.ramp()):
        send(c, req)
    try:
        srv.run()
        raise RuntimeError("the closed loop ran dry before the window closed")
    except WindowClosed:
        pass
    finally:
        if st.span is not None:
            jax.profiler.stop_trace()
    return {"st": st, "steps": steps, "prompt_len": prompt_len,
            "prompts": prompts, "sent": sent, "trace_dir": trace_dir,
            "requests": list(reqs.values())}
