"""Plain float32 reference of Qwen2-MoE (Qwen1.5-MoE-A2.7B) as served.

Per layer: ``x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x))`` with multi-head
attention under rotary embeddings, a softmax router over
``num_experts`` taking the top ``num_experts_per_tok`` (renormalised only
with ``norm_topk_prob``), SwiGLU experts, and one SwiGLU shared expert of
``shared_expert_intermediate_size`` added ungated (the served program has
no ``shared_expert_gate``; the configuration's ``assumed`` says so).
"""
from __future__ import annotations

import sys

import jax.numpy as jnp

from bench.reference import common as C

BF16, F32 = "bfloat16", "float32"


def program_fields(conf: dict) -> dict:
    """The served program's config fields this configuration fixes."""
    return {**C.moe_fields(conf, conf["num_experts"],
                           conf["shared_expert_intermediate_size"]
                           / conf["moe_intermediate_size"]),
            "first_dense": 0, "attn": "gqa",
            "head_dim": conf["hidden_size"] // conf["num_attention_heads"]}


def leaves(conf: dict):
    """(global tensors, per-layer tensors) in the served layout."""
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    E, f = conf["num_experts"], conf["moe_intermediate_size"]
    fs = conf["shared_expert_intermediate_size"]
    std = conf["initializer_range"]
    glob = {"embed/tok": ((V, d), BF16, std),
            "final_norm/scale": ((d,), F32, "ones"),
            "lm_head/w": ((d, V), BF16, std)}
    layer = {"norm1/scale": ((d,), F32, "ones"),
             "attn/wq": ((d, H * hd), BF16, std),
             "attn/wk": ((d, Hkv * hd), BF16, std),
             "attn/wv": ((d, Hkv * hd), BF16, std),
             "attn/wo": ((H * hd, d), BF16, std),
             "norm2/scale": ((d,), F32, "ones"),
             "ffn/router": ((d, E), F32, std),
             "ffn/w_gate": ((E, d, f), BF16, std),
             "ffn/w_up": ((E, d, f), BF16, std),
             "ffn/w_down": ((E, f, d), BF16, std),
             "ffn/shared/w_gate": ((d, fs), BF16, std),
             "ffn/shared/w_up": ((d, fs), BF16, std),
             "ffn/shared/w_down": ((fs, d), BF16, std)}
    return glob, [dict(layer) for _ in range(conf["num_hidden_layers"])]


def layer(x, w, conf, lowp):
    N, T, d = x.shape
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    eps = conf["rms_norm_eps"]
    h = C.rms_norm(x, w["norm1/scale"], eps)
    q = C.mm("ntd,de->nte", h, w["attn/wq"], lowp).reshape(N, T, H, hd)
    k = C.mm("ntd,de->nte", h, w["attn/wk"], lowp).reshape(N, T, Hkv, hd)
    v = C.mm("ntd,de->nte", h, w["attn/wv"], lowp).reshape(N, T, Hkv, hd)
    q, k = C.rope(q, conf["rope_theta"]), C.rope(k, conf["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    p = C.causal_softmax(C.mm("nthd,nshd->nhts", q, k, lowp) / hd ** 0.5)
    o = C.mm("nhts,nshd->nthd", p, v, lowp).reshape(N, T, H * hd)
    x = x + C.mm("nte,ed->ntd", o, w["attn/wo"], lowp)
    h = C.rms_norm(x, w["norm2/scale"], eps)
    y = C.moe(h, w, conf["num_experts_per_tok"], conf["norm_topk_prob"],
              1.0, lowp)
    y = y + C.swiglu(h, w["ffn/shared/w_gate"], w["ffn/shared/w_up"],
                     w["ffn/shared/w_down"], lowp)
    return x + y


def gaps(conf, seed, tokens, targets, control=False):
    return C.gaps(conf, seed, tokens, targets, sys.modules[__name__],
                  control)
