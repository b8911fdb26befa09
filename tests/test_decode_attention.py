"""Sequence-sharded shard_map flash-decode (perf lever P2) correctness.

Runs in a SUBPROCESS because multi-device host meshes require
``--xla_force_host_platform_device_count`` before jax initialises (the main
test process keeps the default single device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp, dataclasses
    from repro.configs import get_smoke_config
    from repro.models import init_params, decode_step, init_cache
    from repro.distributed.sharding import cache_pspecs
    from repro.launch.mesh import make_mesh

    failures = []
    for arch in ["granite-8b", "qwen3-14b", "deepseek-v2-236b",
                 "jamba-v0.1-52b"]:
        cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
        mesh = make_mesh((2, 4), ("data", "model"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        B, S = 4, 64
        cache = jax.tree.map(lambda x: x + 0.01, init_cache(cfg, B, S))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (B, 1)), jnp.int32)
        pos = jnp.int32(S - 1)
        with mesh:
            ref, _ = jax.jit(lambda p, b, c: decode_step(p, cfg, b, c, pos))(
                params, {"tokens": tokens}, cache)
            c_sh = cache_pspecs(jax.eval_shape(lambda: init_cache(cfg, B, S)),
                                mesh, cfg, seq_shard=True)
            cache_s = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                   cache, c_sh)
            out, newc = jax.jit(lambda p, b, c: decode_step(
                p, cfg, b, c, pos, attn_impl="seqshard", mesh=mesh,
                batch_axes=("data",)))(params, {"tokens": tokens}, cache_s)
        rel = float(np.max(np.abs(np.asarray(ref) - np.asarray(out))) /
                    (np.max(np.abs(np.asarray(ref))) + 1e-9))
        if rel > 1e-5:
            failures.append((arch, rel))
        print(arch, rel)
    assert not failures, failures
    print("ALL_OK")
""")


@pytest.mark.slow
def test_seqsharded_decode_matches_default():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ALL_OK" in proc.stdout
