"""Plain float32 reference of DeepSeek-V2(-Lite) as served.

Per layer: ``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``.  Multi-head
latent attention without a query low-rank path: ``q = x W_q`` split into
``qk_nope_head_dim`` and ``qk_rope_head_dim`` parts; ``[c_kv, k_rope] =
x W_kv_a``, ``c_kv`` RMS-normed, ``[k_nope, v] = c_kv W_kv_b`` per head;
one rotary ``k_rope`` shared by every head; softmax scale
``1/sqrt(nope + rope)``.  The first ``first_k_dense_replace`` layers have a
dense SwiGLU of ``intermediate_size``; the rest a softmax router over
``n_routed_experts`` taking the top ``num_experts_per_tok`` (scaled by
``routed_scaling_factor``) plus ``n_shared_experts`` shared experts as one
SwiGLU.  Rotary embedding is plain (the configuration's ``rope_scaling``
type is ``default``) in the half-split layout.
"""
from __future__ import annotations

import sys

from bench.reference import common as C

BF16, F32 = "bfloat16", "float32"


def program_fields(conf: dict) -> dict:
    """The served program's config fields this configuration fixes: MLA
    with no query low-rank path."""
    return {**C.moe_fields(conf, conf["n_routed_experts"],
                           conf["n_shared_experts"]),
            "first_dense": conf["first_k_dense_replace"], "attn": "mla",
            "kv_lora_rank": conf["kv_lora_rank"],
            "qk_nope_dim": conf["qk_nope_head_dim"],
            "qk_rope_dim": conf["qk_rope_head_dim"],
            "v_head_dim": conf["v_head_dim"], "q_lora_rank": 0,
            "d_ff": conf["intermediate_size"]}


def leaves(conf: dict):
    d, V, H = conf["hidden_size"], conf["vocab_size"], conf["num_attention_heads"]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
    E, f = conf["n_routed_experts"], conf["moe_intermediate_size"]
    fs = conf["n_shared_experts"] * f
    F = conf["intermediate_size"]
    std = conf["initializer_range"]
    if conf["q_lora_rank"] is not None or conf["moe_layer_freq"] != 1:
        raise ValueError("reference has no query low-rank path and puts an "
                         "MoE in every layer after the dense ones")
    glob = {"embed/tok": ((V, d), BF16, std),
            "final_norm/scale": ((d,), F32, "ones"),
            "lm_head/w": ((d, V), BF16, std)}
    attn = {"norm1/scale": ((d,), F32, "ones"),
            "attn/wq": ((d, H * (dn + dr)), BF16, std),
            "attn/wkv_a": ((d, r + dr), BF16, std),
            "attn/kv_norm": ((r,), F32, "ones"),
            "attn/wkv_b": ((r, H * (dn + dv)), BF16, std),
            "attn/wo": ((H * dv, d), BF16, std),
            "norm2/scale": ((d,), F32, "ones")}
    dense = {"ffn/w_gate": ((d, F), BF16, std),
             "ffn/w_up": ((d, F), BF16, std),
             "ffn/w_down": ((F, d), BF16, std)}
    moe = {"ffn/router": ((d, E), F32, std),
           "ffn/w_gate": ((E, d, f), BF16, std),
           "ffn/w_up": ((E, d, f), BF16, std),
           "ffn/w_down": ((E, f, d), BF16, std),
           "ffn/shared/w_gate": ((d, fs), BF16, std),
           "ffn/shared/w_up": ((d, fs), BF16, std),
           "ffn/shared/w_down": ((fs, d), BF16, std)}
    n_dense = conf["first_k_dense_replace"]
    return glob, [{**attn, **(dense if l < n_dense else moe)}
                  for l in range(conf["num_hidden_layers"])]


def layer(x, w, conf, lowp):
    N, T, d = x.shape
    H = conf["num_attention_heads"]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
    eps = conf["rms_norm_eps"]
    theta = conf["rope_theta"]
    h = C.rms_norm(x, w["norm1/scale"], eps)
    q = C.mm("ntd,de->nte", h, w["attn/wq"], lowp).reshape(N, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], C.rope(q[..., dn:], theta)
    kv_a = C.mm("ntd,de->nte", h, w["attn/wkv_a"], lowp)
    ckv = C.rms_norm(kv_a[..., :r], w["attn/kv_norm"], eps)
    k_rope = C.rope(kv_a[..., None, r:], theta)[:, :, 0]
    kv = C.mm("ntc,ce->nte", ckv, w["attn/wkv_b"], lowp).reshape(
        N, T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (C.mm("nthd,nshd->nhts", q_nope, k_nope, lowp)
         + C.mm("nthd,nsd->nhts", q_rope, k_rope, lowp)) / (dn + dr) ** 0.5
    o = C.mm("nhts,nshd->nthd", C.causal_softmax(s), v, lowp)
    x = x + C.mm("nte,ed->ntd", o.reshape(N, T, H * dv), w["attn/wo"], lowp)
    h = C.rms_norm(x, w["norm2/scale"], eps)
    if "ffn/router" not in w:
        return x + C.swiglu(h, w["ffn/w_gate"], w["ffn/w_up"],
                            w["ffn/w_down"], lowp)
    if conf["scoring_func"] != "softmax" or conf["topk_method"] != "greedy":
        raise ValueError("reference routes by softmax, greedy top-k")
    y = C.moe(h, w, conf["num_experts_per_tok"], conf["norm_topk_prob"],
              float(conf["routed_scaling_factor"]), lowp)
    y = y + C.swiglu(h, w["ffn/shared/w_gate"], w["ffn/shared/w_up"],
                     w["ffn/shared/w_down"], lowp)
    return x + y


def gaps(conf, seed, tokens, targets, control=False):
    return C.gaps(conf, seed, tokens, targets, sys.modules[__name__],
                  control)
