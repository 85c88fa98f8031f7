"""Operations a served LongCat-Flash rank needs, from the layer shapes: 2 per
multiply-add, matrix multiplications only. Per prompt token really presented
(unpadded; the head once per prompt, for its last position) and per generated
token (attention over its context). The held experts count at their EXPECTED
picks, ``moe_topk * held / router outputs`` a token an expert layer; the zero
experts and the absent experts cost no product here. Prefill attends with
keys and values materialised (192 + 128 products a head a pair), decode with
the projections absorbed (576 + 512 a head a cached position): what each form
of the function needs, not what a kernel chooses to redo.

``latent_bytes_per_token``: what the latent decode attention has to read for
one generated token, the UNPADDED rows of its whole context in every
attention sub-layer. All 64 heads share a row: 64 * (576 + 512) * 2
operations for its 1152 bytes are 121 operations a byte, under the v5e's 240,
so bytes bound the kernel."""

WIDTH = {"float32": 4, "bfloat16": 2}


def _router_outputs(cfg) -> int:
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def _dense_per_token(cfg) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    mla = (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
           + rkv * h * (nope + dv) + h * dv * d)
    ffn = 3 * d * cfg["ffn_hidden_size"]
    picks = cfg["moe_topk"] * cfg["n_routed_experts"] / _router_outputs(cfg)
    moe = d * _router_outputs(cfg) + picks * 3 * d * cfg["expert_ffn_hidden_size"]
    return 2.0 * cfg["num_layers"] * (2 * mla + 2 * ffn + moe)


def _head(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prompt_flops(cfg, prompt_len: int) -> float:
    pair = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    attn = 2.0 * 2 * cfg["num_layers"] * pair * prompt_len * (prompt_len + 1) / 2
    return prompt_len * _dense_per_token(cfg) + attn + _head(cfg)


def decode_flops(cfg, context: int) -> float:
    row = cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return (_dense_per_token(cfg) + 2.0 * 2 * cfg["num_layers"] * row * context
            + _head(cfg))


def serve_flops_per_s(cfg, mix, ctx) -> float:
    """Of the traced run: the prompts whose first token, and the generated
    tokens whose arrival, fell inside the window."""
    t0, t1 = ctx["window"]
    total = 0.0
    for p, times in ctx["tokens"]:
        if t0 <= times[0] <= t1:
            total += prompt_flops(cfg, p)
        for i, t in enumerate(times[1:], start=1):
            if t0 <= t <= t1:
                total += decode_flops(cfg, p + i)
    return total / (t1 - t0)


def latent_bytes_per_token(cfg, context: int) -> float:
    return float(2 * cfg["num_layers"] * context
                 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                 * WIDTH[cfg["param_dtype"]])
