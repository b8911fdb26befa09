"""Model FLOPs of one decoded token, from the configuration's reference
tensor list: two operations per multiply-add of every weight the token
uses (attention, router, shared experts, the top-k of the routed experts,
dense FFNs, the LM head; the embedding lookup is free) plus attention over
``ctx`` earlier positions (scores and values, every head)."""
from bench import spec as S


def flops_per_token(conf: dict, ctx: float) -> float:
    glob, layers = S.reference_module(conf).leaves(conf)
    n = 0.0
    for name, (shape, _, _) in glob.items():
        if name == "lm_head/w":
            n += shape[0] * shape[1]
    for spec in layers:
        for name, (shape, _, _) in spec.items():
            if len(shape) < 2:              # norm scales: no multiply-adds
                continue
            size = 1
            for s in shape:
                size *= s
            if len(shape) == 3:             # routed experts: top-k of them
                size = size // shape[0] * conf["num_experts_per_tok"]
            n += size
    H = conf["num_attention_heads"]
    if "qk_nope_head_dim" in conf:
        qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        v = conf["v_head_dim"]
    else:
        qk = v = conf["hidden_size"] // H
    return 2 * n + 2 * len(layers) * H * ctx * (qk + v)
