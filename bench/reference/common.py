"""Plain float32 pieces shared by the references of this benchmark.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernel, cache,
batching or paging.  ``lowp=True`` is the control: every matrix product
takes operands rounded to float8 e4m3 (scaled per matrix or per row to
the format's range) and accumulates in float32 -- the step below the
bfloat16 the configurations are served in.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def q8(x, axes):
    """``x`` rounded to float8 e4m3 with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq: str, a, b, lowp: bool):
    """``einsum(eq, a, b)``; the control rounds ``a`` per row (last axis)
    and ``b`` per matrix (last two axes) to float8 first."""
    if lowp:
        a, b = q8(a, (-1,)), q8(b, (-2, -1))
    return jnp.einsum(eq, a, b, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """Rotary embedding, half-split layout; x: [N, T, H, D] at 0..T-1."""
    T, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(T, dtype=np.float64)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_softmax(scores):
    """scores: [N, H, T, S] -> probabilities over s <= t."""
    T, S = scores.shape[-2:]
    mask = np.arange(S)[None, :] <= np.arange(T)[:, None]
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)


def swiglu(x, wg, wu, wd, lowp):
    h = jax.nn.silu(mm("ntd,df->ntf", x, wg, lowp)) * mm("ntd,df->ntf", x,
                                                          wu, lowp)
    return mm("ntf,fd->ntd", h, wd, lowp)


def moe(x, w: Dict, top_k: int, norm_topk: bool, scale: float, lowp):
    """Softmax router, top-k, every expert as a plain SwiGLU on every
    token weighted by its gate (0 where not selected)."""
    probs = jax.nn.softmax(mm("ntd,de->nte", x, w["ffn/router"], lowp), -1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    E = probs.shape[-1]
    gates = jnp.sum(jax.nn.one_hot(top_i, E) * top_p[..., None], -2) * scale

    def one(y, e):
        g, (wg, wu, wd) = e
        return y + g[..., None] * swiglu(x, wg, wu, wd, lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.moveaxis(gates, -1, 0),
                         (w["ffn/w_gate"], w["ffn/w_up"], w["ffn/w_down"])))
    return y


def moe_fields(conf: dict, n_experts: int, n_shared) -> dict:
    """The program's config fields that a routed-expert decoder's
    configuration fixes, whatever its attention (``program_fields``)."""
    return {"d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "vocab_size": conf["vocab_size"], "n_experts": n_experts,
            "top_k": conf["num_experts_per_tok"],
            "d_expert": conf["moe_intermediate_size"],
            "n_shared_experts": n_shared,
            "rope_theta": float(conf["rope_theta"]),
            "tie_embeddings": conf["tie_word_embeddings"],
            "router_norm_topk": bool(conf["norm_topk_prob"])}


def head(g: Dict):
    """The LM head [d, V]: ``lm_head/w``, or where the configuration ties
    it (no such tensor) the embedding's transpose."""
    return g["lm_head/w"] if "lm_head/w" in g else g["embed/tok"].T


def final_hidden(conf: dict, seed: int, tokens: np.ndarray, mod,
                 lowp: bool):
    """Embed ``tokens`` [N, T] (scaled by the module's ``embed_scale``,
    if it states one), run every layer of the reference module ``mod``
    with weights drawn one layer at a time, and return the final-normed
    hidden state."""
    glob, layers = mod.leaves(conf)
    g = W.reference_globals(seed, glob)
    x = g["embed/tok"][jnp.asarray(tokens)]
    scale = mod.embed_scale(conf) if hasattr(mod, "embed_scale") else 1
    if scale != 1:
        x = x * scale
    for l, spec in enumerate(layers):
        w = W.reference_layer(seed, l, spec)
        x = jax.jit(mod.layer, static_argnums=(2, 3))(
            x, w, _freeze(conf), lowp)
        del w
    return rms_norm(x, g["final_norm/scale"], conf["rms_norm_eps"]), g


def gaps(conf: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
         mod, control: bool = False):
    """Per position of ``tokens`` [N, T]: how far the logit of
    ``targets`` [N, T] (-1: not compared) lies below the reference's best,
    by the reference module ``mod``: its ``leaves`` and ``layer``, and,
    where it states them, ``embed_scale(conf)`` and ``logits_divisor(conf)``
    (default 1).  A configuration without ``lm_head/w`` ties the head
    (``head``).

    With ``control``, also the same gap of the token the float8 control
    puts first.  Returns numpy arrays [N, T] (0 where not compared)."""
    div = mod.logits_divisor(conf) if hasattr(mod, "logits_divisor") else 1
    h, g = final_hidden(conf, seed, tokens, mod, False)
    tg = jnp.asarray(targets)

    def logits(h, w, lowp):
        lg = mm("ntd,dv->ntv", h, w, lowp)
        return lg / div if div != 1 else lg

    @jax.jit
    def gap_of(h, w, tg):
        lg = logits(h, w, False)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, jnp.maximum(tg, 0)[..., None], -1)[..., 0]
        return lg, jnp.where(tg >= 0, best - got, 0.0)

    lg, gap = gap_of(h, head(g), tg)
    out = {"gap": np.asarray(gap)}
    if control:
        hl, gl = final_hidden(conf, seed, tokens, mod, True)
        top = jnp.argmax(logits(hl, head(gl), True), -1)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, top[..., None], -1)[..., 0]
        out["control_gap"] = np.asarray(jnp.where(tg >= 0, best - got, 0.0))
    return out


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def _freeze(conf: dict) -> "_Frozen":
    return _Frozen(conf)
