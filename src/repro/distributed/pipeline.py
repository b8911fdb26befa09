"""Pipeline parallelism over the ``pod`` axis (GPipe-style microbatching).

Large-scale rationale (DESIGN.md §5): the multi-pod mesh's cross-pod links
(DCN) are much slower than ICI, so cross-pod *gradient all-reduce* (pure DP)
is the multi-pod bottleneck for large models.  Pipelining instead places a
contiguous *stage* of layers on each pod and moves only micro-batch
activations point-to-point (`collective_permute`) — O(B·d) per step instead
of O(params).

Implementation: ``shard_map`` over the pipe axis.  The stacked super-block
params [m, ...] shard their leading dim over ``pipe`` (m % P == 0 required —
see EXPERIMENTS §Dry-run notes for which archs qualify).  The classic GPipe
schedule runs M + P − 1 ticks; each tick every stage processes one live
micro-batch and the boundary activations rotate one hop.

Scope: forward pass (inference / loss eval) for homogeneous decoder stacks;
the dry-run variant proves the schedule lowers and compiles on the
(2, 16, 16) production mesh with the pipe axis mapped onto ``pod``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.transformer import _superblock, stack_layout


def pipeline_forward(stack_params, x_micro, cfg, mesh, *, axis="pod",
                     positions=None, moe_impl="einsum"):
    """Run the stacked decoder blocks as a P-stage pipeline.

    stack_params : stacked super-block params, leading dim m (m % P == 0),
                   sharded P(axis) on that dim.
    x_micro      : [M, B_mb, S, d] micro-batches (replicated over `axis`).
    Returns [M, B_mb, S, d].
    """
    Pn = mesh.shape[axis]
    _, period, m = stack_layout(cfg)
    assert m % Pn == 0, f"stack depth {m} not divisible by {Pn} stages"
    M = x_micro.shape[0]

    def stage_fn(params_local, xs):
        """Run this stage's layers (m/P super-blocks) on one micro-batch."""
        B, S = xs.shape[0], xs.shape[1]
        pos = (positions if positions is not None else
               jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)))

        def blk(h, blk_params):
            h, _, _ = _superblock(blk_params, h, cfg, mode="full",
                                  positions=pos, moe_impl=moe_impl)
            return h, None
        out, _ = jax.lax.scan(blk, xs, params_local)
        return out

    def body(params_local, x_all):
        idx = jax.lax.axis_index(axis)
        n_ticks = M + Pn - 1
        buf = jnp.zeros_like(x_all[0])              # stage input register

        def tick(carry, t):
            buf, acc = carry
            # stage 0 feeds micro-batch t (while in range); others take the
            # rotated boundary activation
            mb = jnp.clip(t, 0, M - 1)
            feed = jax.lax.dynamic_index_in_dim(x_all, mb, 0, keepdims=False)
            x_in = jnp.where((idx == 0) & (t < M), feed, buf)
            y = stage_fn(params_local, x_in)
            # last stage commits its result for micro-batch t - (P-1)
            out_mb = jnp.clip(t - (Pn - 1), 0, M - 1)
            commit = (idx == Pn - 1) & (t >= Pn - 1)
            upd = jax.lax.dynamic_update_slice(
                acc, y[None].astype(acc.dtype), (out_mb,) + (0,) * y.ndim)
            acc = jnp.where(commit, upd, acc)
            # rotate boundary activations one hop forward
            perm = [(i, (i + 1) % Pn) for i in range(Pn)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, acc), None

        acc0 = jnp.zeros_like(x_all)
        (_, acc), _ = jax.lax.scan(tick, (buf, acc0), jnp.arange(n_ticks))
        # only the last stage holds the results; replicate via psum
        return jax.lax.psum(acc, axis)

    in_specs = (P(axis), P())
    out_specs = P()
    out = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)(stack_params, x_micro)
    return out
