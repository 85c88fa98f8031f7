"""Operations a served Xing4.0 stage needs, from the layer shapes: 2 per
multiply-add. Per prompt token really presented (unpadded; the head once per
prompt, for its last position) and per generated token (attention over its
context). An expert layer costs a token its router, ``num_experts_per_tok``
of the routed experts and the shared one; the hyper-connection costs a
sub-layer its ``n*d x (2n + n*n)`` product and its two mixes (``n*d`` to
read, ``n*n*d + n*d`` to write). Prefill attends with keys and values
materialised (192 + 128 products a head a pair), decode with the projections
absorbed (576 + 512 a head a cached position): what each form of the function
needs, not what a kernel chooses to redo.

``latent_bytes_per_token``: what the latent decode attention has to read for
one generated token, the UNPADDED rows of its whole context in every layer.
All 32 heads share a row: 32 * (576 + 512) * 2 operations for its 1152 bytes
are 60 operations a byte, under the v5e's 240, so bytes bound the kernel."""

WIDTH = {"float32": 4, "bfloat16": 2}


def mla_params(cfg) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + dv) + h * dv * d)


def hc_params(cfg) -> int:
    """One sub-layer's ``phi``."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg) -> int:
    return (mla_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            + 2 * hc_params(cfg))


def expert_layer_params(cfg) -> int:
    return (mla_params(cfg) + 2 * hc_params(cfg)
            + cfg["hidden_size"] * cfg["n_routed_experts"]
            + (cfg["n_routed_experts"] + cfg["n_shared_experts"])
            * expert_params(cfg))


def outer_params(cfg) -> int:
    """Embedding and untied head."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def _layers(cfg):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def _hc_per_sublayer(cfg) -> float:
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return hc_params(cfg) + n * d + n * n * d + n * d


def _dense_per_token(cfg) -> float:
    """Multiply-adds x 2 a token outside attention's scores and the head."""
    d = cfg["hidden_size"]
    dense, expert = _layers(cfg)
    active = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    per_dense = 3 * d * cfg["intermediate_size"]
    per_expert = d * cfg["n_routed_experts"] + active * expert_params(cfg)
    every = mla_params(cfg) + 2 * _hc_per_sublayer(cfg)
    return 2.0 * (dense * (every + per_dense) + expert * (every + per_expert))


def _head(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prompt_flops(cfg, prompt_len: int) -> float:
    pair = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    attn = (2.0 * cfg["num_hidden_layers"] * pair
            * prompt_len * (prompt_len + 1) / 2)
    return prompt_len * _dense_per_token(cfg) + attn + _head(cfg)


def decode_flops(cfg, context: int) -> float:
    row = cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return (_dense_per_token(cfg)
            + 2.0 * cfg["num_hidden_layers"] * row * context + _head(cfg))


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
    return float(cfg["num_hidden_layers"] * context
                 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                 * WIDTH[cfg["param_dtype"]])
