"""Slot-indexed ragged grouped-GEMM megakernel tests (kernel family +
serving integration):

* kernel↔oracle parity — the interpret-mode Pallas kernels
  (``slab_ragged_gemm``, ``slab_splice_admit``, ``zip_gemm_grouped``) are
  bit-exact against the ``kernels/ref.py`` jnp oracles and the jitted XLA
  dispatch wrappers in ``kernels/ops.py``, across ragged group shapes
  (singleton groups, repeated slots, non-128-multiple d/f, pad tiles),
* splice-admit aliasing — the fused bit-plane-splice + slab-write kernel
  updates exactly the target slot and byte-preserves every other slot,
* serving parity — ``ffn_impl="ragged"`` logits are bit-identical to the
  padded ``"grouped"`` path in hier / flat / device-cache modes, and the
  batched fused-recovery path to the per-expert loop,
* the acceptance regression — a fully cache-hit device-mode decode step
  stages ZERO weight-copy bytes (``w_copy_bytes``) and ZERO h2d bytes on
  the ragged path, while the pre-megakernel grouped path keeps paying the
  per-step gather copy,
* the stale-SlotRef tripwire — a freed slot's ref crashes the slot-indexed
  weight-source resolution instead of being silently gathered,
* mixed-step staging — a layer-step mixing slab rows and fresh arrays is
  staged in ONE call, byte-identical to the per-expert stack it replaces,
  with one compiled program per count of selected experts,
* pad accounting — under skewed routing the ragged CSR tables compute
  strictly fewer GEMM rows than the pad-to-max-C tables (``pad_frac``).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.slab import DeviceSlabCache
from repro.core.store import build_store
from repro.kernels import moe_gemm, ops, ref
from repro.models import init_params
from repro.serving.zipserve import ZipServer

POOLS = {"F": 2, "C": 2, "S": 2, "E": 2}


@pytest.fixture(scope="module")
def moe2_setup(tmp_path_factory):
    cfg = get_smoke_config("qwen2-moe-a2.7b", n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    d = str(tmp_path_factory.mktemp("store_mk"))
    build_store(params, cfg, d, k_shards=4)
    return cfg, params, d


def _decode(zs, cfg, steps=4, B=2, S=12):
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, 1)),
        jnp.int32)
    caches = zs.init_cache(B, S + steps)
    out, tok = [], tokens
    for i in range(steps):
        lg, caches = zs.decode_step(tok, caches, S - 1 + i)
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        out.append(np.asarray(lg, np.float32))
    return np.stack(out)


# ---------------------------------------------------------------------------
# kernel ↔ oracle parity (interpret-mode Pallas vs jnp refs vs ops dispatch)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d,f,bd,bf", [(16, 32, 16, 32),
                                       (24, 40, 24, 40),     # non-128 dims
                                       (32, 64, 32, 32)])    # tiled f
def test_slab_ragged_gemm_parity(d, f, bd, bf):
    """Interpret kernel == jnp ref == jitted oracle, bitwise, including
    repeated slots (two tiles of one expert) and pad tiles re-aiming at an
    arbitrary resident slot.  Row/column tiling is blocking-invariant on
    the CPU backend, so whole-``d`` blocks are bit-exact against the full
    dot; contraction blocking (block_d < d, the TPU-side accumulation) is
    checked separately to f32 tolerance."""
    rng = np.random.default_rng(0)
    cap, block_c, n_tiles = 4, 8, 6
    buf = jnp.asarray(rng.standard_normal((cap, d, f)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n_tiles * block_c, d)), jnp.float32)
    ts = np.asarray([2, 0, 0, 3, 1, 0], np.int32)   # repeats + "pad" tiles
    out_k = moe_gemm.slab_ragged_gemm(x, buf, ts, block_c=block_c,
                                      block_d=bd, block_f=bf, interpret=True)
    out_r = ref.slab_gemm_ref(x, buf, ts, block_c=block_c)
    out_o = ops.slab_gemm(x, buf, ts, block_c=block_c)   # CPU: XLA oracle
    assert np.array_equal(np.asarray(out_k), np.asarray(out_r))
    assert np.array_equal(np.asarray(out_o), np.asarray(out_r))


def test_slab_ragged_gemm_blocked_contraction_close():
    """block_d < d (the TPU grid's k axis): partial-sum accumulation is
    not bitwise a full dot, but must agree to f32 round-off."""
    rng = np.random.default_rng(4)
    cap, d, f, block_c = 4, 32, 64, 8
    buf = jnp.asarray(rng.standard_normal((cap, d, f)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2 * block_c, d)), jnp.float32)
    ts = np.asarray([3, 1], np.int32)
    out_k = moe_gemm.slab_ragged_gemm(x, buf, ts, block_c=block_c,
                                      block_d=16, block_f=32, interpret=True)
    out_r = ref.slab_gemm_ref(x, buf, ts, block_c=block_c)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-5)


def test_slab_ragged_gemm_singleton_and_empty_tiles():
    """A tile holding a single real token (rest zero-padded) and an
    all-padding tile both reduce to exactly the padded-path rows."""
    rng = np.random.default_rng(1)
    cap, d, f, block_c = 3, 16, 24, 8
    buf = jnp.asarray(rng.standard_normal((cap, d, f)), jnp.float32)
    x = np.zeros((2 * block_c, d), np.float32)
    x[0] = rng.standard_normal(d)          # singleton group in tile 0
    ts = np.asarray([1, 0], np.int32)      # tile 1 is pure padding
    out = np.asarray(moe_gemm.slab_ragged_gemm(
        jnp.asarray(x), buf, ts, block_c=block_c, block_d=d, block_f=f,
        interpret=True))
    full = np.asarray(jnp.einsum("td,df->tf", jnp.asarray(x[:1]), buf[1]))
    assert np.array_equal(out[0], full[0])
    assert np.all(out[1:] == 0.0)          # zero rows -> zero outputs


def test_splice_admit_aliasing_parity():
    """Fused splice+slab-write: target slot gets splice(exp, sm), every
    other slot is byte-preserved through the aliased output — kernel and
    donated oracle both bit-match the jnp ref."""
    rng = np.random.default_rng(2)
    cap, d, f, slot = 4, 16, 32, 2
    base = jnp.asarray(rng.standard_normal((cap, d, f)), jnp.bfloat16)
    w_new = jnp.asarray(rng.standard_normal((d, f)), jnp.bfloat16)
    exp, sm = ref.decompose_bf16_ref(w_new)
    want = np.asarray(ref.splice_admit_ref(base, exp, sm, slot))
    got_k = np.asarray(moe_gemm.slab_splice_admit(
        base, exp, sm, slot, interpret=True))
    assert np.array_equal(got_k.view(np.uint16), want.view(np.uint16))
    got_o = np.asarray(ops.slab_splice_set(
        jnp.array(base), slot, exp.reshape(-1), sm.reshape(-1)))
    assert np.array_equal(got_o.view(np.uint16), want.view(np.uint16))
    assert np.array_equal(got_o[slot].view(np.uint16),
                          np.asarray(w_new).view(np.uint16))


def test_splice_set_donates_buffer():
    """The dispatcher's slab write must consume (donate) the old buffer —
    the whole point is no capacity-sized copy per admit."""
    buf = jnp.zeros((2, 8, 16), jnp.bfloat16)
    w = jnp.ones((8, 16), jnp.bfloat16)
    exp, sm = ref.decompose_bf16_ref(w)
    out = ops.slab_splice_set(buf, 1, exp.reshape(-1), sm.reshape(-1))
    assert buf.is_deleted()
    assert np.array_equal(np.asarray(out[1], np.float32),
                          np.asarray(w, np.float32))


def test_zip_gemm_grouped_parity():
    """Batched fused recovery+GEMM: interpret kernel == jnp ref == ops
    batch dispatcher, bitwise."""
    rng = np.random.default_rng(3)
    E, C, d, f = 3, 8, 16, 32
    x = jnp.asarray(rng.standard_normal((E, C, d)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16)
    exp, sm = ref.decompose_bf16_ref(w)
    want = np.asarray(ref.zip_gemm_grouped_ref(x, exp, sm), np.float32)
    got_k = np.asarray(moe_gemm.zip_gemm_grouped(
        x, exp, sm, block_c=C, block_d=d, block_f=f, interpret=True),
        np.float32)
    got_o = np.asarray(ops.zip_gemm_batch(x, exp, sm), np.float32)
    assert np.array_equal(got_k, want)
    assert np.array_equal(got_o, want)


def test_bucket_rows_rungs():
    got = [ops.bucket_rows(n) for n in (1, 8, 9, 17, 100, 128, 129, 300)]
    assert got == [8, 8, 16, 32, 128, 128, 256, 384]
    assert ops.bucket_rows(3, align=1) == 4       # tile-count bucketing


# ---------------------------------------------------------------------------
# serving parity: megakernel path vs pinned-equal fallbacks
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode_kw", [dict(cache_mode="hier"),
                                     dict(cache_mode="flat"),
                                     dict(cache_mode="hier",
                                          device_cache=True)],
                         ids=["hier", "flat", "device"])
def test_ragged_vs_grouped_bitidentical(moe2_setup, mode_kw):
    """The slot-indexed ragged FFN must reproduce the padded grouped path's
    logits BIT-identically (per-row GEMM results are blocking-invariant
    and the combine sees the same contribution order)."""
    cfg, params, d = moe2_setup
    kw = dict(L=3, pool_sizes=POOLS, prefetch=True, **mode_kw)
    zs_g = ZipServer(params, cfg, d, ffn_impl="grouped", **kw)
    zs_r = ZipServer(params, cfg, d, ffn_impl="ragged", **kw)
    try:
        ref_lg = _decode(zs_g, cfg)
        out_lg = _decode(zs_r, cfg)
        assert np.array_equal(ref_lg, out_lg)
        ov = zs_r.overlap_summary()
        assert ov["tokens_real"] > 0
        assert 0.0 <= ov["pad_frac"] < 1.0
    finally:
        zs_g.close()
        zs_r.close()


def test_zip_batched_vs_loop_bitidentical(moe2_setup):
    """Fused-recovery serving: ONE batched zip_gemm launch per projection
    must match the historical per-expert loop bitwise, and charge its
    plane uploads to h2d_bytes."""
    cfg, params, d = moe2_setup
    kw = dict(L=3, pool_sizes=POOLS, prefetch=True, fused_recovery=True)
    zs_l = ZipServer(params, cfg, d, ffn_impl="loop", **kw)
    zs_b = ZipServer(params, cfg, d, ffn_impl="ragged", **kw)
    try:
        ref_lg = _decode(zs_l, cfg)
        out_lg = _decode(zs_b, cfg)
        assert np.array_equal(ref_lg, out_lg)
        assert zs_b.engine.h2d_bytes > 0   # batched path meters its uploads
    finally:
        zs_l.close()
        zs_b.close()


def _one_fresh(zs):
    """Make ``zs``'s fetches hand back each layer-step's first selected
    expert as fresh device arrays (as an overflow miss no slot took): every
    MoE layer-step of a fully slab-resident server turns mixed."""
    acquire = zs._acquire_experts

    def acquire_one_fresh(layer_idx, ids, batch):
        weights, io_bytes, blocked = acquire(layer_idx, ids, batch)
        weights = dict(weights)
        weights[ids[0]] = {t: r.read() for t, r in weights[ids[0]].items()}
        return weights, io_bytes, blocked

    zs._acquire_experts = acquire_one_fresh


def test_cache_hit_step_zero_w_copy_and_h2d(moe2_setup):
    """Acceptance regression: with every expert slab-resident, a ragged
    decode step stages ZERO weight-copy bytes and ZERO h2d bytes and makes
    no staging call; the pre-megakernel grouped path keeps paying the
    per-step gather copy.  A mixed step then stages each MoE layer in ONE
    call whose rows account for every selected expert, with logits
    bit-identical to the grouped path's."""
    cfg, params, d = moe2_setup
    ample = {"F": cfg.n_experts, "C": 0, "S": 0, "E": 0}
    deltas, mixed_lg = {}, {}
    for impl in ("grouped", "ragged"):
        zs = ZipServer(params, cfg, d, L=3, pool_sizes=ample, prefetch=True,
                       device_cache=True, ffn_impl=impl)
        try:
            for l in zs._moe_layers:       # warm every expert into the slab
                zs.engine.fetch_experts(l, list(range(cfg.n_experts)))
            tokens = jnp.zeros((2, 1), jnp.int32)
            caches = zs.init_cache(2, 18)
            lg, caches = zs.decode_step(tokens, caches, 11)  # jit warmup
            h2d0 = zs.engine.h2d_bytes
            w0 = zs.engine.w_copy_bytes
            ov = zs.overlap_stats
            stage0 = dict(ov)
            tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
            for i in range(3):
                lg, caches = zs.decode_step(tok, caches, 12 + i)
                tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
            deltas[impl] = (zs.engine.h2d_bytes - h2d0,
                            zs.engine.w_copy_bytes - w0)
            n_moe = len(zs._moe_layers)
            assert ov["stage_calls"] == stage0["stage_calls"] == 0
            if impl == "ragged":
                assert all(s["w_copy_bytes"] == 0 for s in
                           zs.stats[-3 * n_moe:])
            _one_fresh(zs)
            stage0 = dict(ov)
            lg, caches = zs.decode_step(tok, caches, 15)
            mixed_lg[impl] = np.asarray(lg, np.float32)
            step = zs.stats[-n_moe:]
            assert all(s["w_copy_bytes"] > 0 for s in step)
            if impl == "ragged":
                rows = {k: ov[k] - stage0[k] for k in
                        ("stage_calls", "stage_rows_slab", "stage_rows_fresh")}
                assert rows["stage_calls"] == n_moe, rows
                assert rows["stage_rows_fresh"] == n_moe, rows
                assert rows["stage_rows_slab"] + rows["stage_rows_fresh"] \
                    == sum(s["n_experts"] for s in step), (rows, step)
        finally:
            zs.close()
    assert deltas["ragged"] == (0, 0), deltas
    assert deltas["grouped"][1] > 0, deltas   # the copy the megakernel kills
    assert np.array_equal(mixed_lg["ragged"], mixed_lg["grouped"])


def test_fused_splice_admit_taken_on_miss(moe2_setup):
    """Demand misses in device mode must land through the fused
    splice-admit (one aliased launch), not a standalone splice + copy-in:
    the slab's own fused-write counter moves."""
    cfg, params, d = moe2_setup
    zs = ZipServer(params, cfg, d, L=3, pool_sizes=POOLS, prefetch=True,
                   device_cache=True)
    try:
        _decode(zs, cfg)
        slabs = [s for s in zs.engine._slabs.values() if s is not None]
        assert sum(s.splice_writes for s in slabs) > 0
        assert zs.overlap_summary()["splice_ops"] > 0   # merged ledger
    finally:
        zs.close()


def test_stale_slotref_trips_ragged_weight_source(moe2_setup):
    """A freed slot's SlotRef reaching the slot-indexed weight resolution
    must crash (the conventions-pass tripwire), never be gathered as the
    slot's new occupant."""
    cfg, params, d = moe2_setup
    zs = ZipServer(params, cfg, d, L=2, pool_sizes=POOLS, prefetch=False,
                   device_cache=True)
    try:
        slab = DeviceSlabCache(9, {"w_up": (4, 8)}, capacity=1)
        refs = slab.put(0, {"w_up": jnp.ones((4, 8), jnp.bfloat16)})
        slab.free(0)                       # generation bump: ref is stale
        weights = {0: {"w_up": refs["w_up"]}}
        with pytest.raises(AssertionError):
            zs._slab_sources("w_up", weights, [0])
    finally:
        zs.close()


def _mixed_step(zs, n, fresh, seed):
    """A layer-step over experts 0..n-1 whose ``fresh`` rows (chosen at
    random) are plain device arrays and whose other rows are SlotRefs into
    a slab at shuffled slots; every expert is routed to by some row."""
    rng = np.random.default_rng(seed)
    shapes = zs.engine._slab(zs._moe_layers[0]).shapes
    slab = DeviceSlabCache(99, shapes, capacity=8)
    slots = rng.permutation(8)
    fresh_rows = set(rng.choice(n, fresh, replace=False).tolist())
    weights = {}
    for e in range(n):
        w = {t: jnp.asarray(rng.standard_normal(s) * 0.05, jnp.bfloat16)
             for t, s in shapes.items()}
        weights[e] = w if e in fresh_rows else slab.put(100 + int(slots[e]), w)
    k = zs.cfg.top_k
    B = -(-n // k) + 1
    top_i = ((np.arange(B)[:, None] * k + np.arange(k)) % n)[:, None, :]
    top_p = rng.uniform(0.1, 1.0, (B, 1, k)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((B, 1, zs.cfg.d_model)), jnp.float32)
    return x, top_p, top_i, weights, list(range(n)), sorted(shapes)


@pytest.mark.parametrize("share", ["one", "half", "all_but_one"])
@pytest.mark.parametrize("n", [3, 5, 8])
def test_mixed_step_staged_in_one_call(moe2_setup, n, share):
    """A mixed layer-step's weight sources come from ONE staging call whose
    stacks are byte-identical to the per-expert stack they replace, charge
    the same ``w_copy_bytes``, and feed a ragged FFN bit-identical to the
    padded grouped path and, within f32 accumulation-order noise, to the
    per-expert loop oracle."""
    cfg, params, d = moe2_setup
    fresh = {"one": 1, "half": n // 2, "all_but_one": n - 1}[share]
    zs = ZipServer(params, cfg, d, L=2, pool_sizes=POOLS, prefetch=False,
                   device_cache=True)
    try:
        x, top_p, top_i, weights, ids, names = _mixed_step(zs, n, fresh, n)
        per_expert = {t: jnp.stack([zs._as_weight(weights[e][t]) for e in ids])
                      for t in names}
        ov = zs.overlap_stats
        w0, ov0 = zs.engine.w_copy_bytes, dict(ov)
        src = zs._slab_sources(names, weights, ids)
        assert ov["stage_calls"] == ov0["stage_calls"] + 1
        assert ov["stage_rows_fresh"] == ov0["stage_rows_fresh"] + fresh
        assert ov["stage_rows_slab"] == ov0["stage_rows_slab"] + n - fresh
        for t in names:
            buf, slots = src[t]
            assert np.array_equal(np.asarray(buf).view(np.uint16),
                                  np.asarray(per_expert[t]).view(np.uint16))
            assert np.array_equal(slots, np.arange(n))
        assert zs.engine.w_copy_bytes - w0 == sum(
            int(w.size) * w.dtype.itemsize for w in per_expert.values())
        y = np.asarray(zs._ffn_ragged(x, top_p, top_i, weights, ids))
        y_g = np.asarray(zs._ffn_grouped(x, top_p, top_i, weights, ids))
        y_l = np.asarray(zs._ffn_loop(x, top_p, top_i, weights))
        assert np.array_equal(y, y_g)
        np.testing.assert_allclose(y, y_l, rtol=1e-5, atol=1e-6)
    finally:
        zs.close()


def test_mixed_step_staging_compiles_once_per_count(moe2_setup):
    """At a fixed count of selected experts, staging with 1, 2 and n-1
    fresh experts (at any rows) runs ONE compiled program: the compiled
    shapes follow the count alone, so a warm-up at one fresh expert covers
    every mixed step of that count."""
    from jax import monitoring
    from repro.serving import zipserve
    cfg, params, d = moe2_setup
    n = 6
    zs = ZipServer(params, cfg, d, L=2, pool_sizes=POOLS, prefetch=False,
                   device_cache=True)
    compiles = []

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    try:
        steps = [_mixed_step(zs, n, fresh, seed)
                 for seed, fresh in enumerate((1, 2, n - 1, 1))]
        _, _, _, weights, ids, names = steps[0]
        jax.block_until_ready(zs._slab_sources(names, weights, ids))
        entries = zipserve._stage_rows._cache_size()
        monitoring.register_event_duration_secs_listener(on_duration)
        try:
            for _, _, _, weights, ids, names in steps[1:]:
                jax.block_until_ready(zs._slab_sources(names, weights, ids))
        finally:
            monitoring.unregister_event_duration_listener(on_duration)
        assert compiles == []
        assert zipserve._stage_rows._cache_size() == entries
        assert zs.overlap_stats["stage_calls"] == len(steps)
    finally:
        zs.close()


def test_ragged_tables_beat_padded_under_skew(moe2_setup):
    """Skewed routing: the CSR ragged tables must compute strictly fewer
    GEMM rows than pad-to-max-C for the same selection (the pad_frac win
    the serving_real benchmark reports)."""
    cfg, params, d = moe2_setup
    zs = ZipServer(params, cfg, d, L=2, pool_sizes=POOLS, prefetch=False)
    try:
        B, k = 16, cfg.top_k
        E = min(8, cfg.n_experts)
        ti = np.zeros((B, 1, k), np.int64)   # bulk: expert 0 drains tokens
        for j in range(1, E):                # singleton trickle experts
            ti[B - 1 - (j - 1) // k, 0, (j - 1) % k] = j
        tp = np.full((B, 1, k), 1.0 / k, np.float32)
        ids = sorted({int(e) for e in ti.reshape(-1)})
        ov = zs.overlap_stats
        r0, p0 = ov["tokens_real"], ov["tokens_padded"]
        zs._gather_by_expert(tp, ti, ids)
        padded_rows = ov["tokens_padded"] - p0
        p1 = ov["tokens_padded"]
        zs._gather_by_expert_ragged(tp, ti, ids)
        ragged_rows = ov["tokens_padded"] - p1
        assert ov["tokens_real"] - r0 == 2 * B * k
        assert ragged_rows < padded_rows, (ragged_rows, padded_rows)
    finally:
        zs.close()
