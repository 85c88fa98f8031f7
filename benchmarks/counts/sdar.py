"""Operations a served SDAR stage needs, from the layer shapes: 2 per
multiply-add, counted as the block schedule RUNS them. A block of ``B``
positions takes ``B + 1`` forward passes (``B`` denoising passes at one
token a pass, then the store pass over the clean block), each over the
block's ``B`` rows a slot: the projections, the router and the
``num_experts_per_tok`` experts of every row, and attention over the cached
context before the block and the block's own ``B`` rows; the head over the
``B`` rows in the denoising passes alone (the store pass reads no logits).
A slot's first block is counted alike, its prompt tail among its rows: it
rides the bank's passes. A prompt costs its whole blocks' rows once
(prefill yields no logits), attention block-causal over them.

``block_bytes_per_pass``: what the block decode attention has to read for
one slot in one pass: every layer's key and value rows of the context
before the block, UNPADDED, and the block's own rows. A key/value head's
32 query rows (4 positions x 8 heads) share its rows: 32 x 128 x 2 x 2
operations for a position's 512 bytes a head are 32 operations a byte,
under the v5e's 240, so bytes bound the kernel."""

WIDTH = {"float32": 4, "bfloat16": 2}


def _attn_proj(cfg) -> int:
    d, hq, hkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    return d * hq * hd * 2 + d * hkv * hd * 2


def _row_flops(cfg, context: int) -> float:
    """One row through every layer, attending to ``context`` positions."""
    d, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = cfg["num_experts_per_tok"] * 3 * d * w
    router = d * cfg["num_experts"]
    scores = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * context
    return 2.0 * cfg["num_hidden_layers"] * (_attn_proj(cfg) + router
                                            + experts + scores)


def _head(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def block_flops(cfg, start: int) -> float:
    """One block at positions ``start .. start + B - 1``, every slot's
    passes."""
    b = int(cfg["block_length"])
    per_pass = b * _row_flops(cfg, start + b)
    return (b + 1) * per_pass + b * b * _head(cfg)


def prompt_flops(cfg, prompt_len: int) -> float:
    b = int(cfg["block_length"])
    whole = prompt_len // b * b
    # block-causal: position t sees (t // B + 1) * B positions
    seen = sum(min((t // b + 1) * b, whole) for t in range(whole))
    rows = whole * _row_flops(cfg, 0)
    return rows + 2.0 * cfg["num_hidden_layers"] * 2 * cfg[
        "num_attention_heads"] * cfg["head_dim"] * seen


def blocks_of(cfg, prompt_len: int, times):
    """(start, arrival) of every block that yielded the served tokens whose
    arrivals are ``times``: a token at ``prompt_len + i`` is in the block
    starting at ``(prompt_len + i) // B * B``; a block's tokens arrive
    together."""
    b = int(cfg["block_length"])
    seen = {}
    for i, t in enumerate(times):
        seen.setdefault((prompt_len + i) // b * b, t)
    return sorted(seen.items())


def serve_flops_per_s(cfg, mix, ctx) -> float:
    """Of the traced run: the prompts whose first tokens, and the blocks
    whose tokens, arrived inside the window."""
    t0, t1 = ctx["window"]
    total = 0.0
    for p, times in ctx["tokens"]:
        if t0 <= times[0] <= t1:
            total += prompt_flops(cfg, p)
        for start, t in blocks_of(cfg, p, times):
            if t0 <= t <= t1:
                total += block_flops(cfg, start)
    return total / (t1 - t0)


def block_bytes_per_pass(cfg, start: int) -> float:
    row = (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
           * WIDTH[cfg["param_dtype"]])
    return float(cfg["num_hidden_layers"] * row
                 * (start + int(cfg["block_length"])))
