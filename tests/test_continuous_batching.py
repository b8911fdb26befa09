"""Continuous batching over the shared multi-tenant expert cache.

The centerpiece is the *differential serving harness*: N staggered requests
served continuously (admitted/retired between decode steps, KV in the shared
page pool, ONE Algorithm-1 block list per step over the union of active
requests) must produce logits **bit-identical** to each request served solo
through the same machinery — in hierarchical, flat, and device-cache modes,
at eviction-inducing pool sizes.  Continuous batching, paging, multi-tenant
cache sharing, and speculative prefetch are all pure scheduling: they may
never change a single bit of any request's output.

Also here: the seeded interleaving fuzz (randomized admit/retire orderings
under ZIPMOE_CHECK=1 with byte-accounting asserts at every retirement), the
KV page pool unit tests (alloc/free/reuse, gather/commit vs the contiguous
``grow_cache``-style reference, leak tripwires), and the BatchServer
retirement edge cases (1-token completions, exact max_len fits, pending
prefetch drained on early EOS retirement)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.models.transformer import init_layer_cache
from repro.serving.kv_cache import KVPagePool
from repro.serving.server import BatchServer
from repro.serving.zipserve import ZipServer

TINY = {"F": 1, "C": 1, "S": 1, "E": 1}          # eviction-inducing


@pytest.fixture(scope="module")
def moe2_setup(tmp_path_factory):
    from repro.core.store import build_store
    cfg = get_smoke_config("qwen2-moe-a2.7b", n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    d = str(tmp_path_factory.mktemp("store_cb"))
    build_store(params, cfg, d, k_shards=4)
    return cfg, params, d


def _serve(cfg, params, d, prompts, *, zs_kw, cc=2, max_new=3, max_len=24,
           arrivals=None, eos=None, max_news=None, on_retire=None):
    """Serve `prompts` through one continuous BatchServer; returns the
    finished Requests in submission order plus the (closed) server pair."""
    zs = ZipServer(params, cfg, d, L=3, prefetch=True, **zs_kw)
    srv = BatchServer(None, cfg, max_batch=cc, max_len=max_len,
                      zip_server=zs, max_concurrency=cc)
    if on_retire is not None:
        srv.on_retire = lambda r: on_retire(srv, zs, r)
    try:
        rids = [srv.submit(p, (max_news[i] if max_news else max_new),
                           arrival_s=(arrivals[i] if arrivals else 0.0),
                           eos_token=eos, record_logits=True)
                for i, p in enumerate(prompts)]
        by = {r.rid: r for r in srv.run()}
        return [by[r] for r in rids], srv, zs
    finally:
        zs.close()


# ---------------------------------------------------------------------------
# differential serving harness
# ---------------------------------------------------------------------------
MODES = [
    pytest.param(dict(pool_sizes=TINY), id="hier-evicting"),
    pytest.param(dict(pool_sizes=TINY, cache_mode="flat", flat_capacity=3),
                 id="flat-evicting"),
    pytest.param(dict(pool_sizes={"F": 2, "C": 2, "S": 2, "E": 2},
                      device_cache=True), id="device-cache"),
]


@pytest.mark.parametrize("zs_kw", MODES)
def test_continuous_bit_identical_to_solo(moe2_setup, zs_kw):
    """N staggered requests served continuously == each served solo, bit for
    bit, even while the shared pools thrash (TINY forces evictions every
    step) and requests at different positions share every decode step."""
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 7, 5)]
    batched, _, _ = _serve(cfg, params, d, prompts, zs_kw=zs_kw, cc=2,
                           arrivals=[0.0, 0.0, 0.02])
    for i, (r, p) in enumerate(zip(batched, prompts)):
        solo, _, _ = _serve(cfg, params, d, [p], zs_kw=zs_kw, cc=1)
        assert solo[0].output == r.output, f"request {i} tokens diverge"
        assert len(solo[0].logits) == len(r.logits) == 3
        for t, (a, b) in enumerate(zip(solo[0].logits, r.logits)):
            assert np.array_equal(a, b), \
                f"request {i} logits differ at output token {t}"


def test_continuous_matches_any_admission_order(moe2_setup):
    """Bit-exactness is interleaving-independent: reversing the arrival
    trace (so admission order flips) changes nothing per-request."""
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 3, 6)]
    fwd, _, _ = _serve(cfg, params, d, prompts, zs_kw=dict(pool_sizes=TINY),
                       cc=2, arrivals=[0.0, 0.01, 0.02])
    rev, _, _ = _serve(cfg, params, d, list(reversed(prompts)),
                       zs_kw=dict(pool_sizes=TINY), cc=2,
                       arrivals=[0.0, 0.01, 0.02])
    for a, b in zip(fwd, reversed(rev)):
        assert a.output == b.output
        for x, y in zip(a.logits, b.logits):
            assert np.array_equal(x, y)


# ---------------------------------------------------------------------------
# seeded interleaving fuzz (runtime checker on)
# ---------------------------------------------------------------------------
def test_interleaving_fuzz_accounting(moe2_setup, monkeypatch):
    """Randomized lengths/budgets/arrivals under ZIPMOE_CHECK=1: after every
    retirement the shared pools' byte accounting must be consistent (no
    pool over capacity, page pool books match live requests) and at the end
    every pin is released, every prefetch drained, every page freed."""
    monkeypatch.setenv("ZIPMOE_CHECK", "1")
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(3, 9, 6)]
    max_news = [int(x) for x in rng.integers(1, 5, 6)]
    arrivals = sorted(float(x) for x in rng.uniform(0.0, 0.08, 6))
    retired = []

    def check(srv, zs, r):
        retired.append(r.rid)
        cs = zs.cache_summary()
        for p, occ in cs["occupancy_bytes"].items():
            assert occ <= cs["capacity_bytes"][p] + 1e-9, (r.rid, p)
        s = srv.pool.summary()
        assert r.rid not in srv.pool._tables          # pages really freed
        assert s["n_requests"] == len(srv.pool._tables)
        assert s["used_bytes"] == (
            s["used_pages"] * srv.pool.page_nbytes()
            + s["used_slots"] * srv.pool.slot_nbytes())

    done, srv, zs = _serve(cfg, params, d, prompts,
                           zs_kw=dict(pool_sizes={"F": 1, "C": 1,
                                                  "S": 2, "E": 2}),
                           cc=3, max_news=max_news, arrivals=arrivals,
                           max_len=16, on_retire=check)
    assert sorted(retired) == sorted(r.rid for r in done)
    assert len(done) == len(prompts)
    for r, mn, p in zip(done, max_news, prompts):
        assert len(r.output) == min(mn, 16 - len(p))
    # balanced pin/unpin on every layer cache
    for cache in zs.engine.caches.values():
        assert not cache.pinned, dict(cache.pinned)
    # all speculative prefetch jobs consumed or drained
    assert all(not v for v in zs._pending.values())
    # page pool fully reclaimed
    assert srv.pool.used_bytes() == 0
    assert srv.pool.summary()["n_requests"] == 0


def test_no_duplicate_chunk_reads_when_pool_ample(moe2_setup):
    """With pools big enough to hold every expert, a whole multi-request
    serve reads each compressed chunk AT MOST once from the store — the
    union-of-requests block list and the residency check must dedup across
    tenants.  Counted per (file, offset) range read, installed after
    construction so engine init-time calibration reads don't count."""
    import collections
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 6, 5)]
    ample = {"F": cfg.n_experts, "C": cfg.n_experts,
             "S": cfg.n_experts, "E": cfg.n_experts}
    zs = ZipServer(params, cfg, d, L=3, prefetch=True, pool_sizes=ample)
    try:
        store = zs.engine.store
        reads = collections.Counter()
        orig = store._read

        def counted(fname, offset, size):
            reads[(fname, offset, size)] += 1
            return orig(fname, offset, size)

        store._read = counted                  # instance attr shadows method
        srv = BatchServer(None, cfg, max_batch=3, max_len=24, zip_server=zs,
                          max_concurrency=3)
        for p in prompts:
            srv.submit(p, 4)
        done = srv.run()
        assert len(done) == len(prompts)
        assert reads, "serve must actually hit the store"
        dups = {k: v for k, v in reads.items() if v > 1}
        assert not dups, f"duplicate chunk reads: {dups}"
    finally:
        zs.close()


# ---------------------------------------------------------------------------
# KV page pool unit tests
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cfg2():
    return get_smoke_config("qwen2-moe-a2.7b", n_layers=2)


def test_page_pool_alloc_free_reuse(cfg2):
    pool = KVPagePool(cfg2, page_size=4, n_pages=6, max_slots=2)
    pool.alloc(1, 10)                                  # 3 pages
    pool.alloc(2, 9)                                   # 3 pages
    assert pool.n_used_pages == 6 and pool.n_used_slots == 2
    assert pool.capacity(1) == 12 and pool.capacity(2) == 12
    with pytest.raises(RuntimeError):
        pool.alloc(3, 1)                               # exhausted (atomic)
    held1 = set(pool._tables[1])
    pool.free(1)
    assert pool.n_used_pages == 3
    pool.alloc(3, 12)                                  # reuses rid 1's pages
    assert set(pool._tables[3]) == held1
    pool.free(2)
    pool.free(3)
    assert pool.n_used_pages == 0 and pool.n_used_slots == 0
    assert pool.used_bytes() == 0                      # leak tripwire
    assert pool.summary()["n_requests"] == 0
    assert pool.pool_bytes() > 0


def test_page_pool_vs_grow_cache(cfg2):
    """gather/commit round-trips through the paged buffers must equal a
    contiguous per-layer cache (the legacy grow_cache layout) written at
    the same positions — same structure, same bytes on the valid prefix."""
    pool = KVPagePool(cfg2, page_size=4, n_pages=8, max_slots=2)
    rid = 7
    pool.alloc(rid, 10)
    cap = pool.capacity(rid)                           # 12, page-aligned
    ref = [init_layer_cache(cfg2, i, 1, cap) for i in range(cfg2.n_layers)]
    for t in range(10):
        views = pool.gather([rid])
        nv, nr = [], []
        for lay_v, lay_r in zip(views, ref):
            dv, dr = {}, {}
            for key in lay_v:
                assert jax.tree.structure(lay_v[key]) == \
                    jax.tree.structure(lay_r[key])     # grow_cache layout
                if key == "kv":                        # sequence leaves
                    val = float(t + 1)
                    dv[key] = jax.tree.map(
                        lambda x: x.at[:, t].set(val), lay_v[key])
                    dr[key] = jax.tree.map(
                        lambda x: x.at[:, t].set(val), lay_r[key])
                else:                                  # seq-free leaves
                    dv[key] = jax.tree.map(
                        lambda x: jnp.full_like(x, float(t)), lay_v[key])
                    dr[key] = jax.tree.map(
                        lambda x: jnp.full_like(x, float(t)), lay_r[key])
            nv.append(dv)
            nr.append(dr)
        pool.commit(nv, [rid], np.asarray([t], np.int32))
        ref = nr
    final = pool.gather([rid])
    for lay_f, lay_r in zip(final, ref):
        for key in lay_f:
            for a, b in zip(jax.tree.leaves(lay_f[key]),
                            jax.tree.leaves(lay_r[key])):
                a, b = np.asarray(a), np.asarray(b)
                if key == "kv":
                    assert np.array_equal(a[:, :10], b[:, :10])
                else:
                    assert np.array_equal(a, b)


def test_page_pool_mixed_length_gather_and_overflow(cfg2):
    pool = KVPagePool(cfg2, page_size=4, n_pages=8, max_slots=3)
    pool.alloc(1, 4)                                   # 1 page
    pool.alloc(2, 11)                                  # 3 pages
    views = pool.gather([1, 2])
    for leaf in jax.tree.leaves(views[0]["kv"]):
        assert leaf.shape[:2] == (2, 12)               # padded to max pages
    # committing past a row's allocation must hard-fail, not corrupt
    with pytest.raises(ValueError):
        pool.commit(views, [1, 2], np.asarray([4, 5], np.int32))
    pool.commit(views, [1, 2], np.asarray([3, 10], np.int32))  # last valid


def test_page_pool_counts_gathered_bytes(cfg2):
    """``gather_bytes`` adds the bytes of every view ``gather`` builds:
    ``T_pad`` (the longest allocation) times the per-token KV bytes of
    every row, plus the seq-free leaves; it only rises."""
    pool = KVPagePool(cfg2, page_size=4, n_pages=8, max_slots=3)
    assert pool.gather_bytes == 0
    pool.alloc(1, 4)                                   # 1 page
    pool.alloc(2, 11)                                  # 3 pages
    views = pool.gather([1, 2])
    one = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(views))
    per_token = pool.page_nbytes() // pool.page_size
    assert one == 2 * 12 * per_token + 2 * pool.slot_nbytes()
    assert pool.gather_bytes == one
    pool.gather([1])                                   # T_pad 4: smaller
    assert one < pool.gather_bytes < 2 * one
    pool.gather([1, 2])
    assert pool.gather_bytes == 2 * one + 4 * per_token + pool.slot_nbytes()


# ---------------------------------------------------------------------------
# BatchServer retirement edge cases
# ---------------------------------------------------------------------------
def test_one_token_completion_metrics(moe2_setup):
    """max_new_tokens=1 requests retire after their first sampled token:
    tpot_s is undefined (None), metrics() must aggregate without it."""
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(3)]
    done, srv, _ = _serve(cfg, params, d, prompts,
                          zs_kw=dict(pool_sizes=TINY), cc=2, max_new=1)
    for r in done:
        assert len(r.output) == 1
        assert r.ttft is not None and r.done is not None
        assert r.tpot_s is None
    m = srv.metrics()
    assert m["n_requests"] == 3 and m["mean_ttft_s"] > 0
    assert "mean_tpot_s" not in m                      # no 2+-token request
    rs = srv.request_summary()
    assert set(rs) == {r.rid for r in done}
    for d_ in rs.values():
        assert d_["n_tokens"] == 1 and d_["tpot_s"] is None
        assert d_["cache_accesses"] > 0                # per-request stats


def test_exact_max_len_fit_mid_batch(moe2_setup):
    """A request whose S + max_new == max_len exactly must complete while
    sharing steps with shorter requests — the last commit lands on the
    final allocated position, never past it."""
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(5)
    max_len = 12
    prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
               rng.integers(0, cfg.vocab_size, 3).astype(np.int32)]
    done, srv, _ = _serve(cfg, params, d, prompts,
                          zs_kw=dict(pool_sizes=TINY), cc=2,
                          max_len=max_len, max_news=[100, 2])
    assert len(done[0].output) == 4                    # clamped to 12 - 8
    assert len(done[1].output) == 2
    assert srv.pool.used_bytes() == 0


def test_eos_retire_drains_pending_prefetch(moe2_setup):
    """EOS mid-decode retires the request early; the speculative prefetch
    jobs issued for steps that now never run must be drained (blocked on,
    credited, dropped) — nothing may leak into _pending or stay pinned."""
    cfg, params, d = moe2_setup
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    # learn the greedy continuation, then replay with its first token as EOS
    probe, _, _ = _serve(cfg, params, d, [prompt],
                         zs_kw=dict(pool_sizes=TINY), cc=1, max_new=4)
    first = probe[0].output[0]
    done, srv, zs = _serve(cfg, params, d, [prompt],
                           zs_kw=dict(pool_sizes=TINY), cc=1, max_new=4,
                           eos=first)
    assert done[0].output == [first]                   # retired on EOS
    assert all(not v for v in zs._pending.values())
    for cache in zs.engine.caches.values():
        assert not cache.pinned
    assert srv.pool.used_bytes() == 0
