"""Memory-coalesced BF16 tensor-recovery kernel (§3.3), TPU-native.

The paper's CUDA kernel streams SM-chunks and decompressed E-chunks through
registers with vectorized loads/stores so the bit splice runs at DRAM
bandwidth.  The TPU analogue (DESIGN.md §2): tile both u8 planes through VMEM
with (block_m, block_n) BlockSpecs aligned to the 8-bit native layout
((32, 128) packing), do the 3-op splice (shift/or/or) on VREGs, and write the
bf16 tile back.  The op is purely memory-bound; the BlockSpec keeps the
HBM→VMEM pipeline saturated and the MXU idle.

Grid: 2-D over (M / block_m, N / block_n).  Inputs must be tile-padded —
``ops.recover_bf16`` runs a tile-aligned 2-D tensor in its own shape and
pads/reshapes any other buffer.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# 8-bit native TPU tiling is (32, 128); use a multiple for fewer grid steps.
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 512


def splice_bf16(exp, sm):
    """The 3-op bit splice on VREGs: (exp u8, sm u8) -> bf16.

    bf16 layout ``s eeeeeeee mmmmmmm``; sm packs the sign in bit 7 and the
    7 mantissa bits in bits 0..6.  Shared by every kernel that recovers
    weights in-flight (this module's recovery kernel, ``moe_gemm.zip_gemm``
    and its grouped variant, and the aliased slab splice-admit) so the bit
    semantics live in exactly one place.

    The shifts run on 32-bit lanes: Mosaic cannot legalize ``shli`` on
    packed 16-bit vectors.  The result is narrowed by an integer truncation
    and reinterpreted by an in-kernel same-width bitcast, never converted
    as a float, so subnormal exponents and NaN payloads survive bit for
    bit.  Outside a kernel it is not so on the TPU: on a v5e, XLA's
    bitcast, reshape, slice, dynamic-update-slice and gather of bf16 each
    flushed subnormals and quieted NaN payloads, so a recovered tensor
    must leave the kernel in its final shape and place."""
    e = exp.astype(jnp.uint32)
    s = sm.astype(jnp.uint32)
    u = ((s & jnp.uint32(0x80)) << 8) | (e << 7) | (s & jnp.uint32(0x7F))
    return jax.lax.bitcast_convert_type(u.astype(jnp.uint16), jnp.bfloat16)


def _recover_kernel(exp_ref, sm_ref, out_ref):
    out_ref[...] = splice_bf16(exp_ref[...], sm_ref[...])


def recover_bf16_2d(exp: jnp.ndarray, sm: jnp.ndarray, *,
                    block_m: int = DEFAULT_BLOCK_M,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool = False) -> jnp.ndarray:
    """exp, sm: u8 [M, N] with M % block_m == 0 and N % block_n == 0."""
    M, N = exp.shape
    assert exp.shape == sm.shape
    assert M % block_m == 0 and N % block_n == 0, (exp.shape, block_m, block_n)
    grid = (M // block_m, N // block_n)
    spec = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    return pl.pallas_call(
        _recover_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bfloat16),
        interpret=interpret,
    )(exp, sm)
