"""Jit'd public wrappers around the Pallas kernels.

On a TPU every dispatcher compiles its kernel with Mosaic.  Elsewhere (the
CPU test hosts) a dispatcher runs its jitted XLA oracle or the kernel in
``interpret=True`` mode; neither is ever taken on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import recovery


def _on_tpu() -> bool:
    """Whether the default device is a TPU.  A backend that fails to
    initialise raises here rather than silently selecting the CPU path."""
    return jax.devices()[0].platform == "tpu"


def _pad_to(x: jnp.ndarray, m: int) -> jnp.ndarray:
    r = (-x.shape[0]) % m
    return jnp.pad(x, (0, r)) if r else x


def bucket_rows(n: int, align: int = 8) -> int:
    """Token-count shape bucket: the next power of two up to 128, then the
    next 128-multiple.  Decode-step token counts drift every step; padding
    each per-expert group (or the padded-path column count) to a fixed rung
    instead of its exact size keeps the GEMM jit cache to a handful of
    shapes instead of recompiling mid-serve (the `pick_block(C, ...)`
    churn).  `align` floors the rung (MXU sublane alignment)."""
    n = max(int(n), align)
    if n <= 128:
        b = align
        while b < n:
            b *= 2
        return b
    return -(-n // 128) * 128


@functools.partial(jax.jit, static_argnames=("shape", "block_m", "block_n",
                                             "interpret"))
def recover_bf16(exp: jnp.ndarray, sm: jnp.ndarray, shape=None, *,
                 block_m: int = None, block_n: int = None,
                 interpret: bool = None) -> jnp.ndarray:
    """Flat (or any-shape) u8 planes -> bf16 array of `shape`.

    A 2-D `shape` that tiles (rows a multiple of 32, columns of 128 — every
    expert tensor of the served models) runs the kernel in its own shape,
    so the bf16 result leaves the kernel untouched.  Any other buffer is
    padded to a 2-D tile-aligned layout and sliced back: bit-exact on the
    CPU, but on a TPU v5e XLA's reshape/slice of bf16 flushed subnormals
    and quieted NaN payloads (507 of the 65,536 patterns).
    """
    from repro.kernels.moe_gemm import pick_block
    interpret = (not _on_tpu()) if interpret is None else interpret
    shape = tuple(shape) if shape is not None else exp.shape
    if (len(shape) == 2 and shape[0] % 32 == 0 and shape[1] % 128 == 0
            and block_m is None and block_n is None):
        return recovery.recover_bf16_2d(
            exp.reshape(shape), sm.reshape(shape),
            block_m=pick_block(shape[0], recovery.DEFAULT_BLOCK_M, 32),
            block_n=pick_block(shape[1], recovery.DEFAULT_BLOCK_N),
            interpret=interpret)
    n = int(exp.size)
    bm = block_m or (8 if interpret else recovery.DEFAULT_BLOCK_M)
    bn = block_n or (128 if interpret else recovery.DEFAULT_BLOCK_N)
    flat_e = _pad_to(exp.reshape(-1), bm * bn)
    flat_s = _pad_to(sm.reshape(-1), bm * bn)
    rows = flat_e.size // bn
    out = recovery.recover_bf16_2d(
        flat_e.reshape(rows, bn), flat_s.reshape(rows, bn),
        block_m=bm, block_n=bn, interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block_c", "block_d", "block_f",
                                             "interpret"))
def grouped_expert_gemm(x: jnp.ndarray, w: jnp.ndarray, *,
                        block_c: int = 128, block_d: int = 512,
                        block_f: int = 128,
                        interpret: bool = None) -> jnp.ndarray:
    """Jit-cached ``moe_gemm.grouped_gemm``: x [E,C,d] @ w [E,d,f] -> [E,C,f].

    The raw ``pallas_call`` builds a fresh jaxpr per invocation; routing this
    through jit makes repeated decode-step shapes hit the compile cache.
    """
    from repro.kernels import moe_gemm
    interpret = (not _on_tpu()) if interpret is None else interpret
    return moe_gemm.grouped_gemm(x, w, block_c=block_c, block_d=block_d,
                                 block_f=block_f, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_d", "block_f",
                                             "interpret"))
def fused_zip_gemm(x: jnp.ndarray, exp: jnp.ndarray, sm: jnp.ndarray, *,
                   block_c: int = 128, block_d: int = 512,
                   block_f: int = 128, interpret: bool = None) -> jnp.ndarray:
    """Jit-cached ``moe_gemm.zip_gemm``: recovery fused into the GEMM."""
    from repro.kernels import moe_gemm
    interpret = (not _on_tpu()) if interpret is None else interpret
    return moe_gemm.zip_gemm(x, exp, sm, block_c=block_c, block_d=block_d,
                             block_f=block_f, interpret=interpret)


# ----------------------------------------------------------------------------
# slot-indexed megakernel entry points (slab-resident expert compute)
# ----------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block_c",))
def _slab_gemm_oracle(x: jnp.ndarray, buf: jnp.ndarray,
                      tile_slot: jnp.ndarray, *, block_c: int) -> jnp.ndarray:
    """Jitted XLA oracle for ``moe_gemm.slab_ragged_gemm`` (non-TPU hosts):
    per-tile slot gather + f32 einsum.  Bit-identical to the Mosaic kernel
    (CPU XLA dots are row-stable across blockings); the internal gather is
    an XLA detail of the emulation — the runtime-level zero-copy contract
    (``w_copy_bytes``) is charged by the serving layer, which stages no
    weight copy on this path."""
    T, d = x.shape
    xt = x.reshape(T // block_c, block_c, d).astype(jnp.float32)
    wt = jnp.take(buf, tile_slot, axis=0).astype(jnp.float32)
    out = jnp.einsum("tcd,tdf->tcf", xt, wt)
    return out.astype(x.dtype).reshape(T, -1)


def slab_gemm(x: jnp.ndarray, buf: jnp.ndarray, tile_slot, *,
              block_c: int = 8, block_d: int = 512, block_f: int = 128,
              interpret: bool = None) -> jnp.ndarray:  # hot-path
    """Slot-indexed ragged grouped GEMM against the whole slab buffer.

    x: [T, d] (tokens CSR-grouped by expert, each group padded to a
    ``block_c`` multiple); buf: [capacity, d, f] — the per-layer
    ``DeviceSlabCache`` buffer read IN PLACE (or a stacked weight batch in
    host mode, with ``tile_slot`` indexing stack rows); tile_slot: int32
    [T // block_c].  TPU: the Mosaic megakernel; elsewhere: the jitted XLA
    oracle (same bits, no interpret-mode grid overhead)."""
    ts = jnp.asarray(tile_slot, jnp.int32)
    if interpret:
        from repro.kernels import moe_gemm
        return moe_gemm.slab_ragged_gemm(x, buf, ts, block_c=block_c,
                                         block_d=block_d, block_f=block_f,
                                         interpret=True)
    if _on_tpu():
        return _slab_gemm_tpu(x, buf, ts, block_c=block_c, block_d=block_d,
                              block_f=block_f)
    return _slab_gemm_oracle(x, buf, ts, block_c=block_c)


@functools.partial(jax.jit, static_argnames=("block_c", "block_d", "block_f"))
def _slab_gemm_tpu(x, buf, tile_slot, *, block_c, block_d, block_f):
    from repro.kernels import moe_gemm
    return moe_gemm.slab_ragged_gemm(x, buf, tile_slot, block_c=block_c,
                                     block_d=block_d, block_f=block_f,
                                     interpret=False)


@functools.partial(jax.jit, donate_argnums=(0,))
def _splice_set_oracle(buf: jnp.ndarray, slot: jnp.ndarray,
                       exp: jnp.ndarray, sm: jnp.ndarray) -> jnp.ndarray:
    """Jitted donated oracle for ``moe_gemm.slab_splice_admit``: one launch
    fusing the bit-plane splice with the slab slot write (the donated buf
    is updated in place — no capacity-sized copy, no standalone spliced
    tensor)."""
    from repro.core import bitfield
    w = bitfield.reconstruct_jnp(exp.reshape(-1),
                                 sm.reshape(-1)).reshape(buf.shape[1:])
    # the update runs on the integer bits: the CPU backend quiets
    # signalling-NaN payloads in a bf16 dynamic-update-slice
    bits = jax.lax.dynamic_update_index_in_dim(
        jax.lax.bitcast_convert_type(buf, jnp.uint16),
        jax.lax.bitcast_convert_type(w, jnp.uint16), slot, 0)
    return jax.lax.bitcast_convert_type(bits, buf.dtype)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=())
def _splice_set_tpu(buf, slot, exp, sm):
    from repro.kernels import moe_gemm
    return moe_gemm.slab_splice_admit(buf, exp.reshape(buf.shape[1:]),
                                      sm.reshape(buf.shape[1:]), slot,
                                      interpret=False)


def slab_splice_set(buf: jnp.ndarray, slot: int, exp: jnp.ndarray,
                    sm: jnp.ndarray) -> jnp.ndarray:
    """Fused splice-admit: write splice(exp, sm) into ``buf[slot]`` of the
    donated slab buffer in ONE kernel launch — a demand miss warms the slab
    as a side effect of its recovery.  TPU: the aliased Mosaic kernel;
    elsewhere: the jitted donated oracle."""
    f = _splice_set_tpu if _on_tpu() else _splice_set_oracle
    return f(buf, jnp.int32(slot), exp, sm)


def splice_planes_device(exp: jnp.ndarray, sm: jnp.ndarray, shape
                         ) -> jnp.ndarray:
    """Standalone splice of ALREADY-uploaded device planes (the fused-admit
    fallback when no slab slot is available): device bf16 out, no h2d."""
    if _on_tpu():
        return recover_bf16(exp, sm, tuple(shape))
    return _recover_oracle(exp, sm, tuple(shape))


@functools.partial(jax.jit, static_argnames=())
def _zip_gemm_batch_oracle(x: jnp.ndarray, exp: jnp.ndarray,
                           sm: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels import ref
    w = ref.recover_bf16_ref(exp, sm)
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_d", "block_f"))
def _zip_gemm_batch_tpu(x, exp, sm, *, block_c, block_d, block_f):
    from repro.kernels import moe_gemm
    return moe_gemm.zip_gemm_grouped(x, exp, sm, block_c=block_c,
                                     block_d=block_d, block_f=block_f,
                                     interpret=False)


def zip_gemm_batch(x: jnp.ndarray, exp: jnp.ndarray, sm: jnp.ndarray, *,
                   block_c: int = 128, block_d: int = 512,
                   block_f: int = 128) -> jnp.ndarray:
    """Batched fused recovery+GEMM over every active expert of a step:
    x [E, C, d] against u8 bit-planes exp/sm [E, d, f] -> [E, C, f].
    One launch replaces ``fused_zip_gemm``'s per-expert Python loop."""
    if _on_tpu():
        return _zip_gemm_batch_tpu(x, exp, sm, block_c=block_c,
                                   block_d=block_d, block_f=block_f)
    return _zip_gemm_batch_oracle(x, exp, sm)


@functools.partial(jax.jit, static_argnames=("shape",))
def _recover_oracle(exp: jnp.ndarray, sm: jnp.ndarray, shape=None
                    ) -> jnp.ndarray:
    """Jitted jnp splice (the kernel's oracle): bit-identical to the Pallas
    kernel, but XLA-compiled instead of grid-interpreted — on non-TPU hosts
    this is ~2 orders of magnitude faster than interpret mode (see
    benchmarks/splice.py), so the device recovery path stays usable on CPU
    CI."""
    from repro.core import bitfield
    return bitfield.reconstruct_jnp(exp.reshape(-1),
                                    sm.reshape(-1)).reshape(shape)


def recover_bf16_device(exp_np, sm_np, shape) -> jnp.ndarray:
    """Engine hook: numpy/bytes planes in, **device** bf16 out.

    Uploads the two u8 planes once and leaves the spliced tensor on device
    for the grouped GEMM (or a slab write) to consume — no d2h download.
    This is the fix for the historical ``recover_bf16_host`` double
    round-trip: device splice -> host ndarray -> re-upload at GEMM time.
    On TPU the splice is the Mosaic kernel; elsewhere the jitted jnp oracle
    (same bits, no interpret-mode grid overhead).
    """
    import numpy as np
    exp = jnp.asarray(np.asarray(exp_np))
    sm = jnp.asarray(np.frombuffer(sm_np, np.uint8)
                     if isinstance(sm_np, (bytes, bytearray))
                     else np.asarray(sm_np))
    if _on_tpu():
        return recover_bf16(exp, sm, tuple(shape))
    return _recover_oracle(exp, sm, tuple(shape))


def recover_bf16_host(exp_np, sm_np, shape):
    """Numpy planes in, numpy bf16 out (via the kernel).

    Pays a d2h download; only for consumers that genuinely need a host
    array — the grouped-GEMM path uses :func:`recover_bf16_device`.
    """
    import numpy as np
    return np.asarray(recover_bf16_device(exp_np, sm_np, shape))
