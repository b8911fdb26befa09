"""Model FLOPs of one decoded token, from the configuration's reference
tensor list: two operations per multiply-add of every weight the token
uses (attention or state-space projections, router, shared experts, the
top-k of the routed experts, dense FFNs, the LM head -- a tied head once,
as the head; the embedding lookup is free), attention over ``ctx``
earlier positions (scores and values, every head) in each layer that
attends, and in each state-space layer the FLOPs its reference module
states for the update and readout of the state (``state_flops(conf)``,
0 where it states none)."""
from bench import spec as S


def _size(shape) -> int:
    size = 1
    for s in shape:
        size *= s
    return size


def _head_sizes(conf: dict):
    """(query heads, query-key width, value width) of an attention layer."""
    H = conf["num_attention_heads"]
    if "qk_nope_head_dim" in conf:
        return (H, conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
                conf["v_head_dim"])
    hd = conf.get("head_dim") or conf["hidden_size"] // H
    return H, hd, hd


def flops_per_token(conf: dict, ctx: float, ref=None) -> float:
    """FLOPs of one token at context ``ctx``, from the configuration's
    reference module (``ref``, else the one it names)."""
    mod = ref or S.reference_module(conf)
    glob, layers = mod.leaves(conf)
    n = float(_size(glob["lm_head/w" if "lm_head/w" in glob
                         else "embed/tok"][0]))
    attends = recurrent = 0
    for spec in layers:
        attends += any(name.startswith("attn/") for name in spec)
        recurrent += any(name.startswith("mamba/") for name in spec)
        for name, (shape, _, _) in spec.items():
            if len(shape) < 2:              # norm scales: no multiply-adds
                continue
            size = _size(shape)
            if len(shape) == 3:             # routed experts: top-k of them
                size = size // shape[0] * conf["num_experts_per_tok"]
            n += size
    H, qk, v = _head_sizes(conf)
    out = 2 * n + 2 * attends * H * ctx * (qk + v)
    if recurrent and hasattr(mod, "state_flops"):
        out += recurrent * mod.state_flops(conf)
    return out
