"""Operations and bytes a served Brumby stage needs, from the layer shapes:
2 per multiply-add. Per prompt token really presented (unpadded; the head
once per prompt, for its last position) and per generated token. Power
retention of degree 2 over the ``hd (hd + 1) / 2`` SYMMETRIC products of a
head (8256 at 128: what the function needs, not the 8320 the program lays
out): a generated token decays and updates each key/value head's state (one
multiply-add an entry) and every query head reads it (one more); a prompt
takes the quadratic form (scores and weighted values, ``2 hd`` products a
head a pair) and one product for the state it leaves. A retention step costs
the same at every context length.

``state_bytes_per_step``: what one decode step has to move for ``slots``
active slots: every layer's and key/value head's state ``S`` and normaliser
``z`` read once and written once, float32, UNPADDED, plus the step's q, k, v
and g. 0.3 operations a byte: bytes bound the kernel."""

WIDTH = {"float32": 4, "bfloat16": 2}


def symmetric_rows(cfg) -> int:
    hd = cfg["head_dim"]
    return hd * (hd + 1) // 2


def retention_params(cfg) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hq * hd + 2 * d * hkv * hd + d * hkv + hkv


def layer_params(cfg) -> int:
    return retention_params(cfg) + 3 * cfg["hidden_size"] * cfg[
        "intermediate_size"]


def outer_params(cfg) -> int:
    """Embedding and untied head."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def slot_state_bytes(cfg) -> int:
    """One slot's state as the function needs it (symmetric rows)."""
    hd = cfg["head_dim"]
    return (cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * symmetric_rows(cfg) * (hd + 1) * WIDTH[cfg["state_dtype"]])


def _matrices_per_token(cfg) -> float:
    return 2.0 * cfg["num_hidden_layers"] * (
        layer_params(cfg) - cfg["num_key_value_heads"])


def _head(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def retention_decode_flops(cfg) -> float:
    """A generated token, all layers: the update of each key/value head's
    state and the read by each query head."""
    hd = cfg["head_dim"]
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    return 2.0 * cfg["num_hidden_layers"] * heads * symmetric_rows(cfg) * hd


def prompt_flops(cfg, prompt_len: int) -> float:
    hd = cfg["head_dim"]
    pairs = prompt_len * (prompt_len + 1) / 2
    form = 2.0 * cfg["num_attention_heads"] * 2 * hd * pairs
    build = (2.0 * cfg["num_key_value_heads"] * symmetric_rows(cfg)
             * (hd + 1) * prompt_len)
    return (prompt_len * _matrices_per_token(cfg)
            + cfg["num_hidden_layers"] * (form + build) + _head(cfg))


def decode_flops(cfg, context: int = 0) -> float:
    return _matrices_per_token(cfg) + retention_decode_flops(cfg) + _head(cfg)


def serve_flops_per_s(cfg, mix, ctx) -> float:
    """Of the traced run: the prompts whose first token, and the generated
    tokens whose arrival, fell inside the window."""
    t0, t1 = ctx["window"]
    total = 0.0
    for p, times in ctx["tokens"]:
        if t0 <= times[0] <= t1:
            total += prompt_flops(cfg, p)
        total += decode_flops(cfg) * sum(
            1 for t in times[1:] if t0 <= t <= t1)
    return total / (t1 - t0)


def state_bytes_per_step(cfg, slots: float) -> float:
    hd = cfg["head_dim"]
    vectors = ((cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
               * hd + cfg["num_key_value_heads"]) * 4
    return float(slots) * (2 * slot_state_bytes(cfg)
                           + cfg["num_hidden_layers"] * vectors)
