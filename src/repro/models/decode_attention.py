"""Sequence-sharded decode attention (beyond-paper perf lever P2).

Baseline decode shards the KV cache on the head/feature dim over ``model``;
GSPMD then re-shards (or outright replicates — "involuntary full
rematerialization") the cache to compute attention, making every decode cell
collective-bound (EXPERIMENTS.md §Roofline).

Here the cache is sharded on the SEQUENCE dim instead and attention runs
under ``shard_map`` as a distributed flash-decode: each shard attends over
its local T/16 slice, then combines with a global max (pmax) + normaliser /
numerator psum — the only cross-chip traffic is O(B·H·D) per layer instead of
O(B·T·H·D) cache movement.

The new token's K/V is written by the shard that owns position ``pos``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_mrope, apply_rope, rms_norm_headwise

NEG_INF = -1e30


def _masked_write(buf, new, rel, in_range):
    """Write `new` [B,1,...] at index rel (clamped) iff in_range.

    O(1) memory traffic: read the old row, select, write one row back —
    never materialises a full-buffer copy (perf iteration 2, §Perf)."""
    idx = (0, jnp.clip(rel, 0, buf.shape[1] - 1)) + (0,) * (buf.ndim - 2)
    old = jax.lax.dynamic_slice(buf, idx, new.shape)
    val = jnp.where(in_range, new.astype(buf.dtype), old)
    return jax.lax.dynamic_update_slice(buf, val, idx)


# ----------------------------------------------------------------------------
# GQA
# ----------------------------------------------------------------------------
def gqa_decode_seqsharded(p, x, cfg, cache, pos, mesh, *, axis="model",
                          batch_axes=None, mrope_positions=None):
    """x: [B,1,d]; cache k/v: [B,T,Hkv,D] sharded P(batch_axes, axis, ...)."""
    B = x.shape[0]
    hd = cfg.head_dim
    T = cache["k"].shape[1]
    n_shards = mesh.shape[axis]
    T_loc = T // n_shards
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm_headwise(p["q_norm"], q)
        k = rms_norm_headwise(p["k_norm"], k)
    if cfg.pos == "rope":
        posv = jnp.full((B, 1), pos, jnp.int32)
        if cfg.mrope and mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, cfg.rope_theta)
            k = apply_mrope(k, mrope_positions, cfg.rope_theta)
        else:
            q = apply_rope(q, posv, cfg.rope_theta)
            k = apply_rope(k, posv, cfg.rope_theta)

    Hkv = cfg.n_kv_heads
    G = cfg.n_heads // Hkv

    def body(q_, k_new, v_new, k_loc, v_loc, pos_):
        idx = jax.lax.axis_index(axis)
        start = idx * T_loc
        rel = pos_ - start
        in_range = (rel >= 0) & (rel < T_loc)
        k_loc = _masked_write(k_loc, k_new, rel, in_range)
        v_loc = _masked_write(v_loc, v_new, rel, in_range)
        Bl = q_.shape[0]
        # bf16 operand reads with f32 MXU accumulation: halves cache traffic
        # vs materialising f32 copies (perf iteration 2, §Perf)
        qf = q_.reshape(Bl, 1, Hkv, G, hd)
        sc = jnp.einsum("bshgd,bthd->bhgst", qf, k_loc,
                        preferred_element_type=jnp.float32) \
            / jnp.sqrt(hd).astype(jnp.float32)
        valid = (start + jnp.arange(T_loc)) <= pos_
        sc = jnp.where(valid[None, None, None, None, :], sc, NEG_INF)
        m_loc = jnp.max(sc, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, axis)
        pexp = jnp.exp(sc - m_glob)
        denom = jax.lax.psum(jnp.sum(pexp, axis=-1, keepdims=True), axis)
        num = jnp.einsum("bhgst,bthd->bshgd", pexp.astype(q_.dtype), v_loc,
                         preferred_element_type=jnp.float32)
        num = jax.lax.psum(num, axis)
        out = (num / jnp.moveaxis(denom, -1, 1)).astype(q_.dtype)
        return out, k_loc, v_loc

    ba = batch_axes
    spec_kv = P(ba, axis, None, None)
    spec_new = P(ba, None, None, None)
    out, ck, cv = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_new, spec_new, spec_new, spec_kv, spec_kv, P()),
        out_specs=(spec_new, spec_kv, spec_kv),
        check_vma=False,
    )(q, k, v, cache["k"], cache["v"], jnp.int32(pos))
    y = out.reshape(B, 1, cfg.n_heads * hd) @ p["wo"]
    return y, {"k": ck, "v": cv}


# ----------------------------------------------------------------------------
# MLA (absorbed, latent cache sequence-sharded)
# ----------------------------------------------------------------------------
def mla_decode_seqsharded(p, x, cfg, cache, pos, mesh, *, axis="model",
                          batch_axes=None):
    """cache: ckv [B,T,C] / k_rope [B,T,R], sharded P(batch_axes, axis, None)."""
    from repro.models.attention import _mla_kv_latent, _mla_q
    B = x.shape[0]
    T = cache["ckv"].shape[1]
    n_shards = mesh.shape[axis]
    T_loc = T // n_shards
    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, posv, cfg.rope_theta)
    ckv_new, k_rope_new = _mla_kv_latent(p, x, cfg, posv)
    scale = 1.0 / jnp.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim).astype(jnp.float32)
    wkv_b = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                               cfg.qk_nope_dim + cfg.v_head_dim)
    w_k = wkv_b[:, :, :cfg.qk_nope_dim].astype(jnp.float32)
    w_v = wkv_b[:, :, cfg.qk_nope_dim:].astype(jnp.float32)
    # absorb the key projection into q: [B,1,H,C]
    q_c = jnp.einsum("bshd,chd->bshc", q_nope.astype(jnp.float32), w_k)

    def body(q_c_, q_r, ckv_new_, kr_new, ckv_loc, kr_loc, pos_):
        idx = jax.lax.axis_index(axis)
        start = idx * T_loc
        rel = pos_ - start
        in_range = (rel >= 0) & (rel < T_loc)
        ckv_loc = _masked_write(ckv_loc, ckv_new_, rel, in_range)
        kr_loc = _masked_write(kr_loc, kr_new, rel, in_range)
        sc = (jnp.einsum("bshc,btc->bhst", q_c_.astype(ckv_loc.dtype), ckv_loc,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,btd->bhst", q_r.astype(kr_loc.dtype), kr_loc,
                           preferred_element_type=jnp.float32)) * scale
        valid = (start + jnp.arange(T_loc)) <= pos_
        sc = jnp.where(valid[None, None, None, :], sc, NEG_INF)
        m_loc = jnp.max(sc, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, axis)
        pexp = jnp.exp(sc - m_glob)
        denom = jax.lax.psum(jnp.sum(pexp, axis=-1, keepdims=True), axis)
        o_c = jax.lax.psum(
            jnp.einsum("bhst,btc->bshc", pexp.astype(ckv_loc.dtype), ckv_loc,
                       preferred_element_type=jnp.float32),
            axis)
        o_c = o_c / jnp.moveaxis(denom, -1, 1)
        return o_c, ckv_loc, kr_loc

    ba = batch_axes
    spec = P(ba, axis, None)
    spec_q = P(ba, None, None, None)
    spec_new = P(ba, None, None)
    o_c, ckv, kr = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_q, spec_q, spec_new, spec_new, spec, spec, P()),
        out_specs=(spec_q, spec, spec),
        check_vma=False,
    )(q_c, q_rope, ckv_new, k_rope_new, cache["ckv"], cache["k_rope"],
      jnp.int32(pos))
    out = jnp.einsum("bshc,chd->bshd", o_c, w_v).astype(x.dtype)
    y = out.reshape(B, 1, cfg.n_heads * cfg.v_head_dim) @ p["wo"]
    return y, {"ckv": ckv, "k_rope": kr}
