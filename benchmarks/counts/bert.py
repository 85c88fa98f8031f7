"""Operations of one BERT MLM training sample, from the layer shapes: 2 per
multiply-add, matrix multiplications only (attention's two included),
forward plus backward (backward = 2 x forward; recomputation not counted).
The embedding tables are looked up, not multiplied, and count nothing."""


def forward_flops_per_token(cfg, seq: int) -> float:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layer = (8 * h * h        # Wq, Wk, Wv, Wo
             + 4 * seq * h    # QK^T and PV over all heads
             + 4 * h * i)     # W1, W2
    head = 2 * h * h + 2 * h * v   # MLM transform, tied decoder (every position)
    return float(cfg["num_hidden_layers"] * layer + head)


def train_flops_per_sample(cfg, mix) -> float:
    return 3.0 * forward_flops_per_token(cfg, mix["seq"]) * mix["seq"]


def param_count(cfg) -> int:
    """Every trained leaf (for the updater's bytes)."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    emb = (v + cfg["max_position_embeddings"] + cfg["type_vocab_size"] + 2) * h
    layer = 4 * (h * h + h) + 2 * h + h * i + i + i * h + h + 2 * h
    heads = (h * h + h) + (h * cfg.get("num_labels", 2)
                           + cfg.get("num_labels", 2)) + (h * h + h + 2 * h + v)
    return emb + cfg["num_hidden_layers"] * layer + heads


def train_flops_per_s(cfg, mix, ctx) -> float:
    """Of the traced run: samples per second x operations per sample."""
    return train_flops_per_sample(cfg, mix) * ctx["rate"]
