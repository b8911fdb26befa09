"""Beyond-paper Pallas kernels for the MoE expert compute hot-spot (the
"expert execution" stage that Algorithm 1 serialises on the accelerator
stream, §3.3; recovery itself is §3.2 / kernels/recovery.py).

1. ``grouped_gemm`` — batched expert GEMM  x[E,C,d] @ w[E,d,f] -> [E,C,f]
   with MXU-aligned (128-multiple) tiles and f32 accumulation over the
   contraction grid axis.  ``ZipServer._ffn_grouped`` gathers a decode
   step's tokens by expert into the [E_active, C, d] batch this consumes —
   replacing the per-batch × per-slot Python loop.

2. ``zip_gemm`` — **fused recovery + GEMM**: the expert weight arrives as the
   two ZipMoE bit-planes (exp u8, sm u8); the kernel splices them to bf16 on
   VREGs and immediately feeds the MXU.  This removes the HBM round-trip of
   the recovered weight (write 2B/elem + read 2B/elem), cutting weight-stream
   traffic 3× for bandwidth-bound decode GEMMs.
   ``zip_gemm_grouped`` is the batched form: one launch over every active
   expert of a decode step instead of a per-expert Python loop.

3. The **slot-indexed megakernel family** — expert compute straight out of
   the ``core/slab.DeviceSlabCache`` buffer, no per-step weight
   materialization:

   * ``slab_ragged_gemm`` — the grouped GEMM takes the whole per-layer slab
     ``[capacity, d, f]`` plus a scalar-prefetched per-token-tile slot
     vector; each tile's weight block is read IN PLACE from its expert's
     slot (``PrefetchScalarGridSpec`` index_map), so the per-step
     ``jnp.take`` gather copy disappears.  Token groups are ragged: tokens
     arrive CSR-concatenated by expert, each group padded only to the tile
     size, so a skewed routing step does FLOPs proportional to its real
     tokens instead of ``E_active × max_count``.
   * ``slab_splice_admit`` — demand-miss recovery lands DIRECTLY in the
     expert's slab slot: the two u8 bit-planes are spliced on VREGs and
     written into the aliased (donated) slab buffer in one launch
     (``input_output_aliases``), warming the slab as a side effect of the
     miss.  Untouched slots pass through by aliasing.

Call through the jit-cached wrappers in ``kernels/ops.py``
(``grouped_expert_gemm``, ``fused_zip_gemm``, ``slab_gemm``,
``slab_splice_set``) — a raw ``pallas_call`` re-traces per invocation and
decode-step shapes must hit the compile cache.  On CPU hosts the wrappers
dispatch to jitted XLA oracles instead (bit-identical, ~100× faster than
interpret-mode grids); the interpret-mode kernels here are exercised by
tests/test_megakernel.py against ``kernels/ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.recovery import splice_bf16


def pick_block(dim: int, cap: int, align: int = 128) -> int:
    """Block size along one dimension: the largest ``align``-multiple that
    is at most ``cap`` and divides ``dim``, else the whole dimension (a
    full-extent block is always legal).  At Qwen1.5-MoE widths this picks
    128 for 1408 = 11 × 128, where a whole-dimension block would overflow
    the v5e scoped VMEM."""
    for b in range(cap - cap % align, 0, -align):
        if dim % b == 0:
            return b
    return dim


# ----------------------------------------------------------------------------
# grouped expert GEMM
# ----------------------------------------------------------------------------
def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_gemm(x: jnp.ndarray, w: jnp.ndarray, *, block_c: int = 128,
                 block_d: int = 512, block_f: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """x: [E, C, d] bf16; w: [E, d, f] bf16 -> [E, C, f] bf16."""
    E, C, D = x.shape
    _, _, F = w.shape
    block_c, block_d, block_f = (min(block_c, C), min(block_d, D),
                                 min(block_f, F))
    assert C % block_c == 0 and D % block_d == 0 and F % block_f == 0
    grid = (E, C // block_c, F // block_f, D // block_d)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, w)


# ----------------------------------------------------------------------------
# slot-indexed ragged grouped GEMM: compute straight out of the device slab
# ----------------------------------------------------------------------------
def _slab_gemm_kernel(ts_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    # ts_ref (the scalar-prefetched tile->slot vector) is consumed by the
    # weight BlockSpec's index_map, not the body — it is passed here because
    # PrefetchScalarGridSpec hands every kernel the scalar operands first.
    del ts_ref
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def slab_ragged_gemm(x: jnp.ndarray, buf: jnp.ndarray,
                     tile_slot: jnp.ndarray, *, block_c: int = 8,
                     block_d: int = 512, block_f: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """x: [T, d] bf16 (tokens CSR-concatenated by expert, every group padded
    to a ``block_c`` multiple); buf: [capacity, d, f] bf16 (the WHOLE slab);
    tile_slot: int32 [T // block_c] mapping each token tile to its expert's
    slab slot.  Returns x @ buf[slot-of-tile] -> [T, f] with the weight rows
    read in place — no gather copy of the active experts is materialized.
    """
    T, D = x.shape
    _, _, F = buf.shape
    block_d, block_f = min(block_d, D), min(block_f, F)
    assert T % block_c == 0 and D % block_d == 0 and F % block_f == 0, \
        (x.shape, buf.shape, block_c, block_d, block_f)
    assert tile_slot.shape == (T // block_c,), (tile_slot.shape, T, block_c)
    grid = (T // block_c, F // block_f, D // block_d)
    return pl.pallas_call(
        functools.partial(_slab_gemm_kernel, n_k=grid[2]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_c, block_d),
                             lambda i, j, k, ts: (i, k)),
                # the slot-indexed read: tile i's weight block comes from
                # slab row ts[i] — scalar-prefetched, resolved per grid step
                pl.BlockSpec((1, block_d, block_f),
                             lambda i, j, k, ts: (ts[i], k, j)),
            ],
            out_specs=pl.BlockSpec((block_c, block_f),
                                   lambda i, j, k, ts: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        interpret=interpret,
    )(jnp.asarray(tile_slot, jnp.int32), x, buf)


# ----------------------------------------------------------------------------
# aliased splice-admit: demand-miss recovery lands straight in its slab slot
# ----------------------------------------------------------------------------
def _splice_admit_kernel(slot_ref, buf_ref, exp_ref, sm_ref, o_ref):
    # slot_ref drives the output BlockSpec; buf_ref is the aliased donated
    # input whose untouched slots flow through to the output unmodified.
    del slot_ref, buf_ref
    o_ref[0] = splice_bf16(exp_ref[...], sm_ref[...])


def slab_splice_admit(buf: jnp.ndarray, exp: jnp.ndarray, sm: jnp.ndarray,
                      slot: jnp.ndarray, *,
                      interpret: bool = False) -> jnp.ndarray:
    """One-launch fused splice + slab write: splice the (exp, sm) u8
    bit-planes [d, f] to bf16 on VREGs and store them into ``buf[slot]`` of
    the donated slab buffer [capacity, d, f] — ``input_output_aliases``
    turns the write in-place, so a demand miss warms the slab as a side
    effect of its recovery instead of paying splice + copy.  Block sizes
    are :func:`pick_block` of each dimension (cap 512)."""
    _, D, F = buf.shape
    assert exp.shape == (D, F) and sm.shape == (D, F), (exp.shape, buf.shape)
    block_d, block_f = pick_block(D, 512), pick_block(F, 512)
    grid = (D // block_d, F // block_f)
    slots = jnp.asarray(slot, jnp.int32).reshape(1)
    return pl.pallas_call(
        _splice_admit_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_d, block_f),
                             lambda i, j, s: (s[0], i, j)),
                pl.BlockSpec((block_d, block_f), lambda i, j, s: (i, j)),
                pl.BlockSpec((block_d, block_f), lambda i, j, s: (i, j)),
            ],
            out_specs=pl.BlockSpec((1, block_d, block_f),
                                   lambda i, j, s: (s[0], i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        # with num_scalar_prefetch=1 the slab buffer is input index 1;
        # aliasing it to the sole output makes the slot write in-place
        input_output_aliases={1: 0},
        interpret=interpret,
    )(slots, buf, exp, sm)


# ----------------------------------------------------------------------------
# fused recovery + GEMM
# ----------------------------------------------------------------------------
def _zip_gemm_kernel(x_ref, exp_ref, sm_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = splice_bf16(exp_ref[...], sm_ref[...])
    acc_ref[...] += jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def zip_gemm(x: jnp.ndarray, exp: jnp.ndarray, sm: jnp.ndarray, *,
             block_c: int = 128, block_d: int = 512, block_f: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """x: [C, d] bf16; exp, sm: u8 [d, f] bit-planes -> x @ splice(exp, sm)."""
    C, D = x.shape
    _, F = exp.shape
    block_c, block_d, block_f = (min(block_c, C), min(block_d, D),
                                 min(block_f, F))
    assert C % block_c == 0 and D % block_d == 0 and F % block_f == 0
    grid = (C // block_c, F // block_f, D // block_d)
    return pl.pallas_call(
        functools.partial(_zip_gemm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_d, block_f), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_d, block_f), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_c, block_f), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, exp, sm)


def _zip_gemm_grouped_kernel(x_ref, exp_ref, sm_ref, o_ref, acc_ref, *,
                             n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = splice_bf16(exp_ref[0], sm_ref[0])
    acc_ref[...] += jnp.dot(x_ref[0], w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def zip_gemm_grouped(x: jnp.ndarray, exp: jnp.ndarray, sm: jnp.ndarray, *,
                     block_c: int = 128, block_d: int = 512,
                     block_f: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """Batched fused recovery+GEMM: x [E, C, d] bf16 against per-expert
    bit-planes exp/sm u8 [E, d, f] -> [E, C, f].  One launch covers every
    active expert of a decode step (the per-expert ``zip_gemm`` loop,
    batched)."""
    E, C, D = x.shape
    _, _, F = exp.shape
    assert exp.shape == sm.shape == (E, D, F), (x.shape, exp.shape, sm.shape)
    block_c, block_d, block_f = (min(block_c, C), min(block_d, D),
                                 min(block_f, F))
    assert C % block_c == 0 and D % block_d == 0 and F % block_f == 0
    grid = (E, C // block_c, F // block_f, D // block_d)
    return pl.pallas_call(
        functools.partial(_zip_gemm_grouped_kernel, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, exp, sm)
