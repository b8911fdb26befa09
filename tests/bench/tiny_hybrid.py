"""Test-only tensor list of a hybrid decoder in the program's layout:
Mamba-2 layers with one GQA attention layer in every
``attn_layer_period``, routed experts on every ``expert_layer_period``-th
layer and a SwiGLU MLP on the rest, and a tied head.  It has what the
harness's layout, checks and costs read (``leaves``, ``program_fields``,
``state_flops``); no forward pass."""
BF16, F32 = "bfloat16", "float32"


def _mamba_sizes(conf):
    di = conf["mamba_expand"] * conf["hidden_size"]
    return (di, conf["mamba_n_groups"], conf["mamba_d_state"],
            di // conf["mamba_headdim"])


def program_fields(conf: dict) -> dict:
    di, g, n, h = _mamba_sizes(conf)
    return {"family": "hybrid", "d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["hidden_size"] // conf["num_attention_heads"],
            "d_ff": conf["intermediate_size"],
            "d_expert": conf["intermediate_size"],
            "n_experts": conf["num_experts"],
            "top_k": conf["num_experts_per_tok"], "n_shared_experts": 0,
            "vocab_size": conf["vocab_size"], "pos": "none",
            "d_inner": di, "ssm_groups": g, "ssm_state": n, "ssm_heads": h,
            "ssm_conv": conf["mamba_d_conv"],
            "tie_embeddings": conf["tie_word_embeddings"]}


def state_flops(conf: dict) -> int:
    """A decoded token's state update (decay and input, a multiply-add
    each) and readout (one multiply-add) per state element."""
    di, g, n, h = _mamba_sizes(conf)
    return 2 * 3 * di * n


def leaves(conf: dict):
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, F = d // H, conf["intermediate_size"]
    E, std = conf["num_experts"], conf["initializer_range"]
    di, g, n, h = _mamba_sizes(conf)
    conv = di + 2 * g * n
    glob = {"embed/tok": ((V, d), BF16, std),
            "final_norm/scale": ((d,), F32, "ones")}
    if not conf["tie_word_embeddings"]:
        glob["lm_head/w"] = ((d, V), BF16, std)
    attn = {"attn/wq": ((d, H * hd), BF16, std),
            "attn/wk": ((d, Hkv * hd), BF16, std),
            "attn/wv": ((d, Hkv * hd), BF16, std),
            "attn/wo": ((H * hd, d), BF16, std)}
    mamba = {"mamba/w_z": ((d, di), BF16, std),
             "mamba/w_x": ((d, di), BF16, std),
             "mamba/w_B": ((d, g * n), BF16, std),
             "mamba/w_C": ((d, g * n), BF16, std),
             "mamba/w_dt": ((d, h), BF16, std),
             "mamba/dt_bias": ((h,), F32, "zeros"),
             "mamba/conv_w": ((conf["mamba_d_conv"], conv), BF16, std),
             "mamba/conv_b": ((conv,), BF16, "zeros"),
             "mamba/A_log": ((h,), F32, "zeros"),
             "mamba/D": ((h,), F32, "ones"),
             "mamba/gate_norm": ((di,), F32, "ones"),
             "mamba/w_out": ((di, d), BF16, std)}
    mlp = {"ffn/w_gate": ((d, F), BF16, std),
           "ffn/w_up": ((d, F), BF16, std),
           "ffn/w_down": ((F, d), BF16, std)}
    moe = {"ffn/router": ((d, E), F32, std),
           "ffn/w_gate": ((E, d, F), BF16, std),
           "ffn/w_up": ((E, d, F), BF16, std),
           "ffn/w_down": ((E, F, d), BF16, std)}

    def layer(l):
        attends = l % conf["attn_layer_period"] == conf["attn_layer_offset"]
        routed = (l % conf["expert_layer_period"]
                  == conf["expert_layer_offset"])
        return {"norm1/scale": ((d,), F32, "ones"),
                **(attn if attends else mamba),
                "norm2/scale": ((d,), F32, "ones"),
                **(moe if routed else mlp)}

    return glob, [layer(l) for l in range(conf["num_hidden_layers"])]
