"""The served path's Pallas kernels, compiled by Mosaic for a described
TPU v5e at Qwen1.5-MoE-A2.7B widths (d_model 2048, expert width 1408), and
the mixed-step weight-staging program at those widths, plus the splice's
bit-exactness over every bf16 pattern on the CPU.

Nothing here runs on a chip: the TPU compiler compiles for a topology that
is described, not attached, so a kernel the chip's compiler would refuse
(an unlegalizable op, a block that overflows VMEM, a misaligned slice)
fails here first.  The topology is described inside a fixture, never at
import time: only one process may hold the TPU library, and every test
worker imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import bitfield
from repro.kernels import moe_gemm, ops, recovery

D_MODEL, D_EXPERT = 2048, 1408
CAP = 16                 # slab slots per layer
TOKENS = 64              # ragged rows: 8 tiles of block_c=8
# (d, f) of the gate/up projections and of the down projection
SHAPES = [(D_MODEL, D_EXPERT), (D_EXPERT, D_MODEL)]


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d,f", SHAPES)
def test_slab_ragged_gemm_compiles_for_v5e(one_chip, d, f):
    x = _spec((TOKENS, d), jnp.bfloat16, one_chip)
    buf = _spec((CAP, d, f), jnp.bfloat16, one_chip)
    ts = _spec((TOKENS // 8,), jnp.int32, one_chip)
    _assert_mosaic(ops._slab_gemm_tpu.lower(
        x, buf, ts, block_c=8, block_d=moe_gemm.pick_block(d, 512),
        block_f=moe_gemm.pick_block(f, 128)))


@pytest.mark.parametrize("d,f", SHAPES)
def test_slab_splice_admit_compiles_for_v5e(one_chip, d, f):
    buf = _spec((CAP, d, f), jnp.bfloat16, one_chip)
    plane = _spec((d * f,), jnp.uint8, one_chip)
    slot = _spec((), jnp.int32, one_chip)
    _assert_mosaic(ops._splice_set_tpu.lower(buf, slot, plane, plane))


@pytest.mark.parametrize("d,f", SHAPES)
def test_recover_bf16_compiles_for_v5e(one_chip, d, f):
    plane = _spec((d * f,), jnp.uint8, one_chip)
    _assert_mosaic(ops.recover_bf16.lower(plane, plane, (d, f),
                                          interpret=False))


@pytest.mark.parametrize("d,f", SHAPES)
def test_grouped_gemm_compiles_for_v5e(one_chip, d, f):
    x = _spec((CAP, 8, d), jnp.bfloat16, one_chip)
    w = _spec((CAP, d, f), jnp.bfloat16, one_chip)
    _assert_mosaic(ops.grouped_expert_gemm.lower(
        x, w, block_c=8, block_d=moe_gemm.pick_block(d, 512),
        block_f=moe_gemm.pick_block(f, 128), interpret=False))


def test_splice_bit_exact_over_all_bf16_patterns():
    """The kernel's splice reproduces every 16-bit pattern — subnormal
    exponents, infinities and NaN payloads included — exactly as the numpy
    splice in ``core/bitfield`` does."""
    pats = np.arange(1 << 16, dtype=np.uint16)
    exp, sm = bitfield.decompose_np(pats.view(bitfield.BF16))
    want = bitfield.reconstruct_np(exp, sm).view(np.uint16)
    assert np.array_equal(want, pats)
    got = recovery.recover_bf16_2d(
        jnp.asarray(exp.reshape(256, 256)), jnp.asarray(sm.reshape(256, 256)),
        block_m=256, block_n=256, interpret=True)
    assert np.array_equal(np.asarray(got).view(np.uint16).reshape(-1), want)


def test_pick_block_divides_and_aligns():
    assert moe_gemm.pick_block(2048, 512) == 512
    assert moe_gemm.pick_block(1408, 512) == 128     # 1408 = 11 * 128
    assert moe_gemm.pick_block(1408, 128) == 128
    assert moe_gemm.pick_block(64, 512) == 64        # below one tile: whole
    assert moe_gemm.pick_block(8, 128) == 8


def test_stage_rows_compiles_for_v5e(one_chip):
    """A mixed layer-step's staging program at Qwen's widest count (8 rows
    × top-4 = 32 selected experts) compiles for the chip, and its working
    memory beyond the stacks it returns stays below one expert's tensors:
    nothing copies the slab or a second set of stacks."""
    from repro.serving.zipserve import _stage_rows
    n = 32
    shapes = [SHAPES[0], SHAPES[0], SHAPES[1]]    # w_gate, w_up, w_down
    bufs = tuple(_spec((CAP,) + s, jnp.bfloat16, one_chip) for s in shapes)
    rows = _spec((len(shapes), n), jnp.int32, one_chip)
    fresh = tuple(tuple(_spec(s, jnp.bfloat16, one_chip) for _ in range(n))
                  for s in shapes)
    mem = _stage_rows.lower(bufs, rows, fresh).compile().memory_analysis()
    expert = len(shapes) * D_MODEL * D_EXPERT * 2
    assert mem.output_size_in_bytes >= n * expert
    assert mem.temp_size_in_bytes < expert
