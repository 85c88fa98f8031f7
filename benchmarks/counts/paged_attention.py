"""Bytes the paged decode attention has to read for one generated token: the
keys and values of its whole context, in every layer, in the cache's type.
Two operations per byte-pair read (a dot and an accumulate per element) are
far under the chip's operations-to-bytes ratio, so bytes bound it."""

WIDTH = {"float32": 4, "bfloat16": 2}


def bytes_per_token(cfg, context: int) -> float:
    return float(cfg["n_layer"] * 2 * context * cfg["n_embd"]
                 * WIDTH[cfg["param_dtype"]])
