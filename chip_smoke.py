"""Smoke test of the served ZipMoE path on a TPU, at published widths.

Serves qwen1.5-moe-a2.7b (d_model 2048, 16 x 128 heads, vocab 151936, 60
routed top-4 experts of width 1408, 4 shared experts) with the depth cut to
4 layers and random weights from a fixed seed, through continuous batching
(``BatchServer``) over ``ZipServer(device_cache=True, ffn_impl="ragged")``,
built by ``repro.launch.serve.build_zipmoe_server`` exactly as
``python -m repro.launch.serve --mode zipmoe-batch --layers 4
--device-cache`` builds it.  Phases, each of which fails the run:

1. device  -- JAX's first device must be a TPU.
2. splice  -- all 65,536 bf16 bit patterns through
   ``ops.recover_bf16_device`` and ``ops.slab_splice_set`` on the chip,
   bit-equal to the numpy splice of ``core/bitfield`` (the lossless claim).
3. resident reference -- ``BatchServer(params, cfg)`` with every expert on
   the device: greedy tokens and logits of 4 requests x 16 prompt tokens x
   16 new tokens.
4. ZipMoE -- build the compressed store (set-up), release the experts'
   device copy, and serve the same requests from a 16-slot slab per layer,
   so steps miss.  Fails on an errored request, a failed expert, a worker
   restart, no slab write, no Mosaic kernel in the expert-FFN program, or
   logits outside the tolerance of ``compare_logits``.
5. numbers -- TTFT/TPOT medians, tokens/s, compile seconds and device bytes
   in use, labelled with the device kind: information, not a benchmark.

``--chips 4`` runs only the peer-HBM phase: the same requests through
``ZipServer(mesh_devices=4)`` (misses served from neighbour chips' HBM)
and through ``ZipServer(mesh_devices=1)`` (host decode) at an equal byte
budget; the peer run must serve from the link and emit the same tokens.

The last line of standard output is ``{"ok": true, "device": {...}}``; a
failure exits non-zero without it.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the peer-HBM phase on four chips
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen1.5-moe-a2.7b"
LAYERS = 4            # depth cut of the one-chip run (24 published)
PEER_LAYERS = 2       # depth cut of the four-chip run: the link, not depth,
                      # is what that phase checks
SEED = 0
N_REQUESTS, PROMPT_LEN, MAX_NEW = 4, 16, 16
SLAB_SLOTS = 16       # F-pool slab slots per layer, well under 60 experts
POOLS = {"F": SLAB_SLOTS, "C": 8, "S": 8, "E": 16}
# Logit tolerance, as relative L2 error ||zip - ref|| / ||ref|| of a row.
# Both paths compute in bf16 (8-bit significand: 2^-9 relative rounding per
# operation) but in different orders: the reference prefills the prompt in
# one pass through the dispatch einsums; ZipMoE consumes it one token per
# step through the ragged GEMM with an f32 combine.  On the CPU backend at
# reduced widths (d_model 512, 60 experts top-4, 3 seeds) that put the
# median row at 0.017-0.022, about the gap between the bf16 and f32
# resident paths.  A router near-tie can flip one of a token's top-4
# experts in one path only, which moved single rows by up to 0.38 there.
# A reference that dropped a few over-capacity tokens gave a median of
# 0.087, and unrelated logits sit near sqrt(2), where a broken splice or a
# wrong slab slot would put most rows.  So the median row must stay under
# 2^-4 and every row under 2^-1.
TOL_MEDIAN, TOL_ROW = 2.0 ** -4, 2.0 ** -1


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def device_phase(want_chips: int):
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX's first device is on the "
                               f"{d.platform!r} platform")
    check(len(devs) >= want_chips,
          f"{want_chips} chips wanted, {len(devs)} visible")
    return info


def _pattern_mismatches(got, want):
    """Mismatch count, with how many are NaN payloads and subnormals."""
    bad = np.nonzero(got != want)[0]
    e, m = (want[bad] >> 7) & 0xFF, want[bad] & 0x7F
    nan = int(np.sum((e == 255) & (m != 0)))
    sub = int(np.sum((e == 0) & (m != 0)))
    return (f"{len(bad)} of 65536 patterns differ (NaN payloads {nan}, "
            f"subnormals {sub}; e.g. "
            f"{[(hex(int(want[i])), hex(int(got[i]))) for i in bad[:3]]})")


def splice_phase():
    """Every bf16 bit pattern through both device splice entry points,
    read back by plain device-to-host transfers (bit-exact; an XLA bitcast
    to uint16 on the TPU is not)."""
    from repro.core import bitfield
    from repro.kernels import ops

    pats = np.arange(1 << 16, dtype=np.uint16)
    exp, sm = bitfield.decompose_np(pats.view(bitfield.BF16))
    want = bitfield.reconstruct_np(exp, sm).view(np.uint16)
    check(np.array_equal(want, pats), "numpy splice is not the identity")
    got = np.asarray(ops.recover_bf16_device(exp, sm, (256, 256)))
    got = got.view(np.uint16).reshape(-1)
    check(np.array_equal(got, want), "recover_bf16_device: "
          + _pattern_mismatches(got, want))
    buf = jnp.full((2, 256, 256), 1.5, jnp.bfloat16)
    out = np.asarray(ops.slab_splice_set(buf, 1, jnp.asarray(exp),
                                         jnp.asarray(sm))).view(np.uint16)
    got = out[1].reshape(-1)
    check(np.array_equal(got, want), "slab_splice_set: "
          + _pattern_mismatches(got, want))
    check(np.all(out[0] == 0x3FC0), "slab_splice_set wrote outside its slot")
    print("splice: 65536/65536 bf16 patterns bit-exact through "
          "recover_bf16_device and slab_splice_set")


def make_prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
            for _ in range(N_REQUESTS)]


def serve(srv, prompts):
    """Submit every prompt (greedy, logits recorded) and serve the queue;
    returns the finished requests in submission order."""
    for p in prompts:
        srv.submit(p, MAX_NEW, record_logits=True)
    t0 = time.perf_counter()
    done = sorted(srv.run(), key=lambda r: r.rid)
    return done, time.perf_counter() - t0


def compare_logits(ref, got):
    """Relative L2 error of every logits row both runs computed from the
    same token history (a row after the first differing greedy token has a
    different input and is not compared)."""
    errs, same = [], 0
    for r, g in zip(ref, got):
        same += r.output == g.output
        for t, (a, b) in enumerate(zip(r.logits, g.logits)):
            if t and r.output[t - 1] != g.output[t - 1]:
                break
            errs.append(float(np.linalg.norm(b - a) / np.linalg.norm(a)))
    return np.asarray(errs), same


def mosaic_in_ffn(cfg):
    """The expert-FFN dispatcher holds a Mosaic kernel at served shapes:
    gate/up [T, d_model] x slab [slots, d_model, d_expert] and down."""
    from repro.kernels import ops
    from repro.kernels.moe_gemm import pick_block
    tokens = 8 * 8
    for d, f in ((cfg.d_model, cfg.d_expert), (cfg.d_expert, cfg.d_model)):
        text = ops._slab_gemm_tpu.lower(
            jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((SLAB_SLOTS, d, f), jnp.bfloat16),
            jax.ShapeDtypeStruct((tokens // 8,), jnp.int32),
            block_c=8, block_d=pick_block(d, 512),
            block_f=pick_block(f, 128)).compile().as_text()
        if "tpu_custom_call" not in text:
            return False
    return True


def one_chip(tag: str, compile_s):
    from repro.kernels import ops
    from repro.launch import serve as serve_lib
    from repro.serving.server import BatchServer

    cfg = serve_lib.model_config(ARCH, LAYERS)
    print(f"config: {ARCH} at published widths (d_model={cfg.d_model} "
          f"heads={cfg.n_heads}x{cfg.head_dim} vocab={cfg.vocab_size} "
          f"experts={cfg.n_experts} top{cfg.top_k} d_expert={cfg.d_expert} "
          f"shared={cfg.n_shared_experts}), depth cut to {cfg.n_layers} of "
          f"24 layers, weights random from seed {SEED}")
    prompts = make_prompts(cfg, SEED)
    params = serve_lib.init_model(cfg, SEED)

    # the reference must drop no token, as ZipMoE never does: a per-group
    # expert capacity of the whole sequence (capacity_factor = E / k)
    ref_cfg = dataclasses.replace(cfg,
                                  capacity_factor=cfg.n_experts / cfg.top_k)
    ref_srv = BatchServer(params, ref_cfg, max_batch=N_REQUESTS,
                          max_len=PROMPT_LEN + MAX_NEW)
    ref, ref_s = serve(ref_srv, prompts)
    rm = ref_srv.metrics()
    print(f"resident [{tag}]: {N_REQUESTS} requests in {ref_s:.2f}s "
          f"ttft_p50={rm['ttft_p50_s']:.4f}s "
          f"throughput={rm['throughput_tok_s']:.2f}tok/s")
    del ref_srv

    store_dir = tempfile.mkdtemp(prefix="chip_smoke_store_")
    try:
        t0 = time.perf_counter()
        srv = serve_lib.build_zipmoe_server(
            params, cfg, store_dir, max_batch=N_REQUESTS,
            max_len=PROMPT_LEN + MAX_NEW, pool_sizes=dict(POOLS),
            device_cache=True, ffn_impl="ragged")
        print(f"setup: store + ZipServer in {time.perf_counter() - t0:.1f}s")
        del params
        zs = srv.zip
        try:
            got, zip_s = serve(srv, prompts)
            m = srv.metrics()
            fs, ov = zs.fault_summary(), zs.overlap_summary()
        finally:
            zs.close()
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    failed = [r.rid for r in got if r.error]
    check(not failed, f"requests failed: {failed}: "
                      f"{[r.error for r in got if r.error]}")
    check(fs["failed_experts"] == 0, f"failed_experts={fs['failed_experts']}")
    check(fs["worker_restarts"] == 0,
          f"worker_restarts={fs['worker_restarts']}")
    check(ov["slab_writes"] > 0, "no slab write: no step missed the slab")
    check(ops._slab_gemm_tpu._cache_size() > 0
          and ops._splice_set_tpu._cache_size() > 0,
          "the served path did not dispatch the Mosaic GEMM and splice-admit")
    check(mosaic_in_ffn(cfg), "no tpu_custom_call in the expert FFN")
    errs, same = compare_logits(ref, got)
    med, worst = float(sorted(errs)[len(errs) // 2]), float(errs.max())
    print(f"logits: {len(errs)} rows compared, rel-L2 median={med:.3e} "
          f"max={worst:.3e} (limits {TOL_MEDIAN:.3e} / {TOL_ROW:.3e}); "
          f"greedy tokens identical in {same}/{len(ref)} requests")
    check(len(errs) >= len(ref), "fewer logits rows than requests")
    check(med <= TOL_MEDIAN and worst <= TOL_ROW,
          "logits outside the tolerance of the resident path")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"zipmoe [{tag}]: {N_REQUESTS} requests x {PROMPT_LEN}+{MAX_NEW} "
          f"tokens in {zip_s:.2f}s ttft_p50={m['ttft_p50_s']:.4f}s "
          f"tpot_p50={m['tpot_p50_s']:.4f}s "
          f"throughput={m['throughput_tok_s']:.2f}tok/s "
          f"hit_rate={m['cache_hit_rate']:.3f} "
          f"slab_writes={ov['slab_writes']} "
          f"h2d={ov['h2d_bytes'] / 1e9:.2f}GB")
    print(f"device [{tag}]: compile_s={compile_s[0]:.1f} "
          f"bytes_in_use={stats.get('bytes_in_use')} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def four_chips(tag: str):
    from repro.core.store import drop_routed_experts
    from repro.launch import serve as serve_lib
    from repro.serving.server import BatchServer
    from repro.serving.zipserve import ZipServer

    cfg = serve_lib.model_config(ARCH, PEER_LAYERS)
    print(f"config: {ARCH} at published widths, depth cut to "
          f"{cfg.n_layers} of 24 layers, weights random from seed {SEED}")
    prompts = make_prompts(cfg, SEED)
    params = serve_lib.init_model(cfg, SEED)
    expert_bytes = 3 * cfg.d_model * cfg.d_expert * 2
    # equal per-device byte budget for both runs: a quarter of the experts
    budget = 0.25 * cfg.n_experts * expert_bytes * cfg.n_layers
    kw = dict(max_batch=N_REQUESTS, max_len=PROMPT_LEN + MAX_NEW,
              mem_budget=budget, replan_every=4)
    store_dir = tempfile.mkdtemp(prefix="chip_smoke_peer_")
    try:
        host_srv = serve_lib.build_zipmoe_server(params, cfg, store_dir,
                                                 **kw)
        try:
            host, host_s = serve(host_srv, prompts)
        finally:
            host_srv.zip.close()
        zs = ZipServer(drop_routed_experts(params), cfg, store_dir,
                       mesh_devices=4, mem_budget=budget, replan_every=4)
        peer_srv = BatchServer(None, cfg, max_batch=N_REQUESTS,
                               max_len=PROMPT_LEN + MAX_NEW, zip_server=zs)
        try:
            peer, peer_s = serve(peer_srv, prompts)
            ps, fs = zs.peer_summary(), zs.fault_summary()
        finally:
            zs.close()
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    for name, done in (("host", host), ("peer", peer)):
        bad = [r.error for r in done if r.error]
        check(not bad, f"{name} run: requests failed: {bad}")
    check(fs["failed_experts"] == 0, f"failed_experts={fs['failed_experts']}")
    check(ps["served"] > 0, f"the peer tier served nothing: {ps}")
    check([r.output for r in peer] == [r.output for r in host],
          "peer-served tokens differ from the one-device run")
    print(f"peer [{tag}]: served={ps['served']} fallbacks={ps['fallbacks']} "
          f"collective_bytes={ps['total_bytes']} "
          f"put_bytes={ps['peer_put_bytes']} in {peer_s:.2f}s; "
          f"host decode in {host_s:.2f}s; tokens identical in "
          f"{N_REQUESTS}/{N_REQUESTS} requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the peer-HBM phase on four chips")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        from repro.launch import serve as serve_lib
    except ImportError as e:
        print(f"chip_smoke: FAILED: the repository's sources are not "
              f"beside this script ({e})", file=sys.stderr)
        return 2
    serve_lib.enable_compile_cache()
    from jax import monitoring

    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        info = device_phase(args.chips)
        tag = f"{info['kind']} x{args.chips}"
        if args.chips == 4:
            four_chips(tag)
        else:
            splice_phase()
            one_chip(tag, compile_s)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
