"""Device-resident expert slab tests (the zero-copy recovery→GEMM pipeline):

* DeviceSlabCache unit invariants — donated in-place writes, slot
  alloc/free, generation counters invalidating stale refs, gather,
* engine device_cache mode — slab slots track F-pool residency (reuse
  after eviction, pin-while-resident), stale SlotRefs are never
  re-admitted as if they still named the old expert's weights,
* losslessness — slab-path logits are bit-identical to host-path logits
  over a replayed decode (hier AND flat cache modes),
* the acceptance regression — a fully cache-hit decode step performs ZERO
  host→device expert-weight transfer (`engine.h2d_bytes` flat), while the
  host path keeps paying the per-step re-upload.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.engine import ExpertPayload, ZipMoEEngine
from repro.core.slab import DeviceSlabCache, SlotRef
from repro.core.states import CState
from repro.core.store import ExpertStore, build_store
from repro.models import init_params
from repro.serving.zipserve import ZipServer

POOLS = {"F": 2, "C": 2, "S": 2, "E": 2}


@pytest.fixture(scope="module")
def moe2_setup(tmp_path_factory):
    cfg = get_smoke_config("qwen2-moe-a2.7b", n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    d = str(tmp_path_factory.mktemp("store_slab"))
    build_store(params, cfg, d, k_shards=4)
    return cfg, params, d


# ---------------------------------------------------------------------------
# DeviceSlabCache unit invariants
# ---------------------------------------------------------------------------
def test_slab_put_gather_roundtrip():
    slab = DeviceSlabCache(0, {"w": (4, 8)}, capacity=3)
    rng = np.random.default_rng(0)
    vals = {e: jnp.asarray(rng.standard_normal((4, 8)), jnp.bfloat16)
            for e in (5, 9)}
    refs5 = slab.put(5, {"w": vals[5]})
    refs9 = slab.put(9, {"w": vals[9]})
    assert refs5["w"].valid and refs9["w"].valid
    assert slab.slot_of.keys() == {5, 9}
    got = slab.gather("w", [slab.slot_of[9], slab.slot_of[5]])
    assert np.array_equal(np.asarray(got[0]), np.asarray(vals[9]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(vals[5]))
    # per-ref device read agrees with the gather
    assert np.array_equal(np.asarray(refs5["w"].read()),
                          np.asarray(vals[5]))


def test_slab_free_bumps_generation_and_reuses_slot():
    slab = DeviceSlabCache(0, {"w": (2, 4)}, capacity=1)
    ref_a = slab.put(7, {"w": jnp.ones((2, 4), jnp.bfloat16)})["w"]
    slot_a = slab.slot_of[7]
    slab.free(7)
    assert not ref_a.valid                 # generation bump -> stale
    assert 7 not in slab
    ref_b = slab.put(3, {"w": jnp.zeros((2, 4), jnp.bfloat16)})["w"]
    assert slab.slot_of[3] == slot_a       # slot actually reused
    assert ref_b.valid and not ref_a.valid
    with pytest.raises(AssertionError):
        ref_a.read()                       # stale refs refuse to read


def test_slab_donated_write_is_in_place():
    """The slot write donates the slab buffer: the pre-write array object
    must actually be consumed (no silent capacity-sized copy per admit)."""
    slab = DeviceSlabCache(0, {"w": (2, 2)}, capacity=2)
    old = slab.bufs["w"]
    slab.put(0, {"w": jnp.ones((2, 2), jnp.bfloat16)})
    assert old.is_deleted()


def test_fused_admit_keeps_every_bf16_pattern():
    """A demand miss's fused splice-admit stores all 65,536 bf16 bit
    patterns unchanged in its slot, signalling-NaN payloads and subnormals
    included (the store's lossless claim)."""
    from repro.core import bitfield
    from repro.core.slab import DevicePlanes
    pats = np.arange(1 << 16, dtype=np.uint16)
    exp, sm = bitfield.decompose_np(pats.view(bitfield.BF16))
    slab = DeviceSlabCache(0, {"w": (256, 256)}, capacity=2)
    slab.put(2, {"w": DevicePlanes(jnp.asarray(exp), jnp.asarray(sm),
                                   (256, 256))})
    got = np.asarray(slab.bufs["w"])[slab.slot_of[2]].view(np.uint16)
    assert np.array_equal(got.reshape(-1), pats)


def test_slab_capacity_overflow_asserts():
    slab = DeviceSlabCache(0, {"w": (1, 1)}, capacity=1)
    slab.put(0, {"w": jnp.zeros((1, 1), jnp.bfloat16)})
    with pytest.raises(AssertionError):
        slab.put(1, {"w": jnp.zeros((1, 1), jnp.bfloat16)})


# ---------------------------------------------------------------------------
# engine device_cache mode: slot lifecycle against F-pool residency
# ---------------------------------------------------------------------------
def test_engine_device_fetch_bitexact_and_slab_resident(moe2_setup):
    cfg, params, d = moe2_setup
    store = ExpertStore(d)
    eng = ZipMoEEngine(store, n_experts=cfg.n_experts, n_layers=cfg.n_layers,
                       L=3, pool_sizes=POOLS, device_cache=True)
    try:
        out, _ = eng.fetch_experts(0, [0, 1])
        for e in (0, 1):
            ref = store.load_group((0, e))
            for name, arr in out[e].items():
                v = arr.read() if isinstance(arr, SlotRef) else arr
                assert np.array_equal(np.asarray(v, np.float32),
                                      np.asarray(ref[name], np.float32))
        slab = eng._slab(0)
        assert slab is not None and set(slab.slot_of) == {0, 1}
        # F-pool payloads hold valid SlotRefs, nothing else
        for e, ent in eng.caches[0].pools["F"].items():
            assert all(isinstance(v, SlotRef) and v.valid
                       for v in ent.payload.full.values())
        # a second fetch is a pure F hit served from the slab: no new
        # plane uploads, no new slab writes
        h2d0, w0 = eng.h2d_bytes, slab.writes
        out2, _ = eng.fetch_experts(0, [0, 1])
        assert eng.h2d_bytes == h2d0 and slab.writes == w0
        assert all(isinstance(v, SlotRef)
                   for w in out2.values() for v in w.values())
    finally:
        eng.shutdown()


def test_slot_freed_and_reused_after_eviction(moe2_setup):
    cfg, params, d = moe2_setup
    eng = ZipMoEEngine(ExpertStore(d), n_experts=cfg.n_experts,
                       n_layers=cfg.n_layers, L=2, delta=0,
                       pool_sizes={"F": 1, "C": 0, "S": 0, "E": 0},
                       device_cache=True)
    try:
        eng.fetch_experts(0, [0])
        slab = eng._slab(0)
        assert set(slab.slot_of) == {0}
        slot0 = slab.slot_of[0]
        ref0 = slab.refs(0)["w_up"]
        # make expert 1 strictly hotter; its admission evicts expert 0
        eng.fetch_experts(0, [1])
        eng.fetch_experts(0, [1])
        assert eng.caches[0].residency(0) is CState.M
        assert set(slab.slot_of) == {1}
        assert slab.slot_of[1] == slot0        # slot reused...
        assert not ref0.valid                  # ...and the old ref is stale
        # the new occupant's F entry reads the NEW expert's weights
        w1 = ExpertStore(d).load_group((0, 1))["w_up"]
        got = np.asarray(slab.refs(1)["w_up"].read(), np.float32)
        assert np.array_equal(got, np.asarray(w1, np.float32))
    finally:
        eng.shutdown()


def test_stale_slotref_payload_never_readmitted(moe2_setup):
    """A payload carrying stale SlotRefs (a speculative tail whose expert
    was evicted mid-flight) must not re-enter the F pool as if the slot
    still held its weights."""
    cfg, params, d = moe2_setup
    eng = ZipMoEEngine(ExpertStore(d), n_experts=cfg.n_experts,
                       n_layers=cfg.n_layers, L=2, delta=0,
                       pool_sizes={"F": 1, "C": 0, "S": 0, "E": 0},
                       device_cache=True)
    try:
        eng.fetch_experts(0, [0])
        slab = eng._slab(0)
        stale = dict(eng.caches[0].pools["F"][0].payload.full)
        eng.fetch_experts(0, [1])
        eng.fetch_experts(0, [1])              # evicts 0, frees its slot
        assert all(not v.valid for v in stale.values())
        # direct re-admission attempt with the stale payload
        eng.trackers[0].record([0, 0, 0])      # make 0 rank-eligible again
        placed = eng.caches[0].admit(0, ExpertPayload(full=stale))
        assert placed is None                  # demote hook refused it
        assert eng.caches[0].residency(0) is CState.M
        assert set(slab.slot_of) == {1}        # slab untouched
    finally:
        eng.shutdown()


def test_pinned_resident_keeps_slab_slot(moe2_setup):
    """Pin-while-resident: a pinned F-resident can never lose its slot to
    a hotter expert's admission (its weights may be mid-step in the FFN)."""
    cfg, params, d = moe2_setup
    eng = ZipMoEEngine(ExpertStore(d), n_experts=cfg.n_experts,
                       n_layers=cfg.n_layers, L=2, delta=0,
                       pool_sizes={"F": 1, "C": 0, "S": 0, "E": 0},
                       device_cache=True)
    try:
        eng.fetch_experts(0, [0])
        slab = eng._slab(0)
        ref0 = slab.refs(0)["w_up"]
        eng.pin_experts(0, [0])
        eng.fetch_experts(0, [1])
        eng.fetch_experts(0, [1])              # hotter, but 0 is pinned
        assert 0 in eng.caches[0].pools["F"]
        assert set(slab.slot_of) == {0} and ref0.valid
        eng.unpin_experts(0, [0])
        eng.fetch_experts(0, [1])              # unpinned: eviction resumes
        assert set(slab.slot_of) == {1} and not ref0.valid
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# serving-level: losslessness + the zero-h2d acceptance regression
# ---------------------------------------------------------------------------
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


@pytest.mark.parametrize("cache_mode", ["hier", "flat"])
def test_slab_vs_host_serving_bitidentical(moe2_setup, cache_mode):
    """Losslessness: device-slab serving must produce bit-identical logits
    to host-path serving over a replayed trace, in both cache modes."""
    cfg, params, d = moe2_setup
    kw = dict(L=3, pool_sizes=POOLS, prefetch=True, cache_mode=cache_mode)
    zs_h = ZipServer(params, cfg, d, **kw)
    zs_d = ZipServer(params, cfg, d, device_cache=True, **kw)
    try:
        ref = _decode(zs_h, cfg)
        out = _decode(zs_d, cfg)
        assert np.array_equal(ref, out)
        ov = zs_d.overlap_summary()
        assert ov["device_cache"] and ov["splice_ops"] > 0
        assert ov["slab_writes"] > 0
    finally:
        zs_h.close()
        zs_d.close()


def test_pinned_resident_not_demoted_by_own_readmission(moe2_setup):
    """Regression: pins block downward re-dispatch, not just victimhood.
    A pinned F-resident whose activation rank has meanwhile dropped below
    the F band used to be demoted to S by its OWN collect-time
    re-admission — freeing its slab slot while the step's returned weights
    still held the SlotRef.  It must stay in F (slot intact) until
    unpinned."""
    cfg, params, d = moe2_setup
    store = ExpertStore(d)
    eng = ZipMoEEngine(store, n_experts=cfg.n_experts, n_layers=cfg.n_layers,
                       L=2, delta=1,
                       pool_sizes={"F": 2, "C": 2, "S": 2, "E": 0},
                       device_cache=True)
    try:
        eng.fetch_experts(0, [0])              # 0 -> F (rank 0)
        eng.pin_experts(0, [0])                # mid-step pin
        for _ in range(3):                     # 1,2,3 strictly hotter:
            eng.fetch_experts(0, [1, 2, 3])    # rank(0) drops past τ_F=3
        assert eng.trackers[0].rank(0) >= 3
        assert 0 in eng.caches[0].pools["F"]   # pinned: never evicted
        # re-selection of 0 while still pinned: its own re-admission must
        # not demote it out of F, and the returned weights must be live
        out, _ = eng.fetch_experts(0, [0])
        ref = store.load_group((0, 0))
        for name, arr in out[0].items():
            v = arr.read() if isinstance(arr, SlotRef) else arr
            assert np.array_equal(np.asarray(v, np.float32),
                                  np.asarray(ref[name], np.float32))
        slab = eng._slab(0)
        assert 0 in eng.caches[0].pools["F"] and 0 in slab
        eng.unpin_experts(0, [0])
        # unpinned: a hotter non-resident's admission evicts 0 again, and
        # the slab slot is released with it
        hot = next(e for e in (1, 2, 3)
                   if e not in eng.caches[0].pools["F"])
        eng.fetch_experts(0, [hot])
        assert 0 not in eng.caches[0].pools["F"] and 0 not in slab
    finally:
        eng.shutdown()


def test_cross_layer_device_cache_bitidentical_under_eviction(tmp_path):
    """Regression: device_cache + cross_layer_depth with eviction-inducing
    pools used to crash on a stale SlotRef (a cross-layer drain admits into
    a later layer's cache before that layer's step pins exist, freeing a
    slot another pending job had seeded as an F no-op).  The engine now
    re-loads such tensors from the store at collect time — logits must be
    bit-identical to host mode, not just crash-free."""
    cfg = get_smoke_config("deepseekv2-lite")
    params = init_params(jax.random.PRNGKey(0), cfg)
    d = str(tmp_path)
    build_store(params, cfg, d, k_shards=4)
    small = {"F": 2, "C": 1, "S": 2, "E": 2}
    kw = dict(L=2, pool_sizes=small, prefetch=True, cross_layer_depth=1)
    zs_h = ZipServer(params, cfg, d, **kw)
    zs_d = ZipServer(params, cfg, d, device_cache=True, **kw)
    try:
        ref = _decode(zs_h, cfg, steps=6)
        out = _decode(zs_d, cfg, steps=6)
        assert np.array_equal(ref, out)
    finally:
        zs_h.close()
        zs_d.close()


def test_cache_hit_step_moves_zero_h2d_bytes(moe2_setup):
    """Acceptance regression: with every expert F-resident in the device
    slab, a decode step transfers ZERO expert-weight bytes host→device;
    the host path keeps re-uploading every step."""
    cfg, params, d = moe2_setup
    ample = {"F": cfg.n_experts, "C": 0, "S": 0, "E": 0}
    deltas = {}
    for name, kw in (("host", {}), ("device", dict(device_cache=True))):
        zs = ZipServer(params, cfg, d, L=3, pool_sizes=ample, prefetch=True,
                       **kw)
        try:
            for l in zs._moe_layers:       # warm every expert into F
                zs.engine.fetch_experts(l, list(range(cfg.n_experts)))
            tokens = jnp.zeros((2, 1), jnp.int32)
            caches = zs.init_cache(2, 18)
            lg, caches = zs.decode_step(tokens, caches, 11)  # jit warmup
            h2d0 = zs.engine.h2d_bytes
            tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
            for i in range(3):
                lg, caches = zs.decode_step(tok, caches, 12 + i)
                tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
            deltas[name] = zs.engine.h2d_bytes - h2d0
            if name == "device":
                assert all(s["h2d_bytes"] == 0 for s in
                           zs.stats[-3 * len(zs._moe_layers):])
        finally:
            zs.close()
    assert deltas["device"] == 0, deltas
    assert deltas["host"] > 0, deltas      # the tax the slab removes
