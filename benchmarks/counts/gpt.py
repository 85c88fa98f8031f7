"""Operations a served decoder needs, from the layer shapes: 2 per
multiply-add, matrix multiplications only. Per prompt token really presented
(unpadded; the head once per prompt, for its last position) and per
generated token (attention over its context)."""


def _dense_per_token(cfg) -> float:
    h = cfg["n_embd"]
    i = cfg.get("n_inner") or 4 * h
    return float(cfg["n_layer"] * (8 * h * h + 4 * h * i))


def _head(cfg) -> float:
    return 2.0 * cfg["n_embd"] * cfg["vocab_size"]


def prompt_flops(cfg, prompt_len: int) -> float:
    attn = cfg["n_layer"] * 4.0 * cfg["n_embd"] * prompt_len * (prompt_len + 1) / 2
    return prompt_len * _dense_per_token(cfg) + attn + _head(cfg)


def decode_flops(cfg, context: int) -> float:
    return (_dense_per_token(cfg) + cfg["n_layer"] * 4.0 * cfg["n_embd"] * context
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
