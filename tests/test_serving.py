"""Serving substrate tests: generate loop, KV growth, batch server."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import forward, init_cache, init_params, prefill
from repro.serving.generate import generate, sample_tokens
from repro.serving.kv_cache import (cache_bytes, grow_cache, restack_layers,
                                    unstack_layers)
from repro.serving.server import BatchServer


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("granite-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_generate_greedy_deterministic(setup):
    cfg, params = setup
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    out1, m1 = generate(params, cfg, prompt, max_new_tokens=6)
    out2, m2 = generate(params, cfg, prompt, max_new_tokens=6)
    assert np.array_equal(out1, out2)
    assert out1.shape == (2, 14)
    assert m1["ttft_s"] > 0 and m1["tpot_s"] > 0


def test_generate_matches_teacher_forcing(setup):
    """Greedy generation then teacher-forced forward: each generated token
    must be the argmax of the full forward at its position."""
    cfg, params = setup
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    out, _ = generate(params, cfg, prompt, max_new_tokens=4)
    toks = jnp.asarray(out)
    logits, _, _ = jax.jit(lambda p, b: forward(p, cfg, b))(
        params, {"tokens": toks})
    for i in range(4):
        pos = 8 + i - 1
        pred = int(np.argmax(np.asarray(logits[0, pos], np.float32)))
        assert pred == int(out[0, 8 + i]), i


def test_grow_cache(setup):
    cfg, params = setup
    pb = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    _, cache = jax.jit(lambda p, b: prefill(p, cfg, b))(params, pb)
    grown = grow_cache(cfg, cache, 2, 32)
    ref = init_cache(cfg, 2, 32)
    assert jax.tree.structure(grown) == jax.tree.structure(ref)
    assert cache_bytes(grown) == cache_bytes(ref)


def test_unstack_restack_roundtrip(setup):
    cfg, params = setup
    cache = init_cache(cfg, 2, 16)
    layers = unstack_layers(cache, cfg)
    assert len(layers) == cfg.n_layers
    back = restack_layers(layers, cfg, cache)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        assert a.shape == b.shape


def test_batch_server(setup):
    cfg, params = setup
    srv = BatchServer(params, cfg, max_batch=4)
    rng = np.random.default_rng(0)
    for _ in range(6):
        srv.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=4)
    done = srv.run()
    assert len(done) == 6
    for r in done:
        assert len(r.output) == 4
        assert r.ttft is not None and r.done is not None
    m = srv.metrics()
    assert m["n_requests"] == 6 and m["throughput_tok_s"] > 0


def test_batch_server_records_logits_on_epoch_path(setup):
    """The resident (epoch) path captures one logits row per emitted token
    when asked, as the continuous path does; greedy picks each argmax."""
    cfg, params = setup
    srv = BatchServer(params, cfg, max_batch=2)
    rng = np.random.default_rng(3)
    for _ in range(2):
        srv.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3,
                   record_logits=True)
    for r in srv.run():
        assert len(r.logits) == len(r.output) == 3
        assert [int(np.argmax(row)) for row in r.logits] == r.output


def test_model_config_published_widths_with_depth_cut():
    from repro.launch.serve import model_config
    cfg = model_config("qwen1.5-moe-a2.7b", 4)
    assert (cfg.n_layers, cfg.d_model, cfg.d_expert, cfg.vocab_size,
            cfg.n_experts, cfg.top_k) == (4, 2048, 1408, 151936, 60, 4)
    assert model_config("qwen1.5-moe-a2.7b").d_model == 256   # smoke preset
    with pytest.raises(ValueError):
        model_config("qwen1.5-moe-a2.7b", 25)


def test_build_zipmoe_server_serves_from_store_only(tmp_path):
    """The entry-point stack: the store is built, the routed experts' device
    buffers are released, and the same requests come back from ZipMoE with
    logits within bf16 noise of a dropless resident reference."""
    import dataclasses

    from repro.launch.serve import build_zipmoe_server, init_model
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    params = init_model(cfg, 0)
    ref_cfg = dataclasses.replace(cfg,
                                  capacity_factor=cfg.n_experts / cfg.top_k)
    prompts = [np.random.default_rng(i).integers(0, cfg.vocab_size, 8)
               for i in range(2)]

    def serve(srv):
        for p in prompts:
            srv.submit(p, max_new_tokens=2, record_logits=True)
        return sorted(srv.run(), key=lambda r: r.rid)

    ref = serve(BatchServer(params, ref_cfg, max_batch=2, max_len=16))
    experts = params["decoder"]["stack"]["sub_0"]["ffn"]["w_up"]
    srv = build_zipmoe_server(params, cfg, str(tmp_path), max_batch=2,
                              max_len=16, device_cache=True,
                              pool_sizes={"F": 2, "C": 1, "S": 1, "E": 2})
    try:
        assert experts.is_deleted()
        assert all("w_up" not in lp["ffn"] for lp in srv.zip.layers)
        got = serve(srv)
    finally:
        srv.zip.close()
    assert not any(r.error for r in got)
    for r, g in zip(ref, got):
        a, b = r.logits[0], g.logits[0]          # same prompt, same input
        assert np.linalg.norm(b - a) / np.linalg.norm(a) < 2 ** -4


def test_sampling_temperature():
    logits = jnp.asarray([[0.0, 10.0, 0.0]])
    greedy = sample_tokens(logits, jax.random.PRNGKey(0), 0.0)
    assert int(greedy[0]) == 1
    hot = [int(sample_tokens(logits, jax.random.PRNGKey(i), 50.0)[0])
           for i in range(40)]
    assert len(set(hot)) > 1                    # high temp actually samples


def test_routing_trace_collection_and_planning():
    """Real router statistics feed the cache planner end to end."""
    import numpy as np
    import jax
    from repro.configs import get_smoke_config
    from repro.core.planner import PlanConsts
    from repro.models import init_params
    from repro.serving.trace import collect_routing_trace, fit_plan_from_trace

    cfg = get_smoke_config("qwen2-moe-a2.7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab_size, (1, 16)).astype(np.int32)
               for _ in range(6)]
    traces = collect_routing_trace(params, cfg, batches)
    assert len(traces) == cfg.n_layers          # every layer is MoE here
    for layer, tr in traces.items():
        assert len(tr) == 6
        for sel in tr:
            assert sel and all(0 <= e < cfg.n_experts for e in sel)
    consts = PlanConsts(u=1.0, v=0.1, c=0.15, L=3, K=4, n_tensors=3)
    plan = fit_plan_from_trace(traces[0], cfg, mem_budget=10.0,
                               bytes_per_state={"F": 2.0, "C": 1.4,
                                                "S": 1.0, "E": 0.4},
                               consts=consts, step=0.25)
    assert abs(sum(plan.ratios.values()) - 1.0) < 1e-9
    assert plan.cost >= 0
