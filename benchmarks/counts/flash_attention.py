"""Operations and bytes of flash attention over one training sample, forward
and backward, all layers, from the shapes: what the function needs, not what
a kernel chooses to redo.

Operations, 2 per multiply-add: forward QK^T and PV (2 matmuls of T x T x d
a head); backward the scores again (flash keeps no T x T matrix), dV, dP, dQ
and dK (5). The program's two backward kernels each recompute the scores and
dP, 9 matmuls in all: the 2 extra count as time, not as work. Padded keys
are multiplied like any other and count.

Bytes: q, k, v read and o written going forward; q, k, v, o, dO read and dQ,
dK, dV written going backward; each T x H in the parameters' type. The row
statistics (lse, delta) are a few per cent of that and are left out.

At BERT-base's d = 64 and T = 512 the two bounds lie close (0.46 ms of
operations, 0.37 ms of bytes a layer at batch 32 on a v5e); ``least_seconds``
takes the larger and says which."""

WIDTH = {"float32": 4, "bfloat16": 2}


def per_sample(cfg, seq: int):
    """(operations, bytes) of one sample through every layer."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    flops = layers * 7 * 2.0 * seq * seq * h
    moved = layers * 12.0 * seq * h * WIDTH[cfg["param_dtype"]]
    return flops, moved


def least_seconds(cfg, seq: int, samples: float, peaks):
    """(seconds, which bound) for ``samples`` samples on one chip."""
    flops, moved = per_sample(cfg, seq)
    by_flops = samples * flops / peaks["flops_per_s"]
    by_bytes = samples * moved / peaks["bytes_per_s"]
    return (by_flops, "operations") if by_flops >= by_bytes else (
        by_bytes, "bytes")
