"""The one general traffic generator. A mix is a data file of parameters
(``traffic/<mix>.json``); this turns it and a seed into requests.

Every seed gets the SAME work: the sizes are the quantiles of the mix's
distributions (a pool of ``pool`` requests), paired and ordered by fixed
shuffles and dealt into one hand per client; the seed deals the hands to the
clients in another order and draws every token id. A window shorter than the
pool therefore sees the same sizes whatever the seed (with the order of the
whole pool drawn from the seed, the number of admissions in a 40 s window
swung by 4% between seeds and tokens/s with it: PERF.md), and two seeds
differ no more than two runs of one seed do.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles of ``spec``'s distribution, clipped
    to [min, max], as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown dist {dist!r}")
    lo, hi = spec.get("min", -np.inf), spec.get("max", np.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def request_sizes(mix: Dict[str, Any]) -> List[tuple]:
    """The pool's (prompt length, new tokens), the same for every seed."""
    n = int(mix["pool"])
    prompt_len = quantiles(mix["prompt_len"], n)
    new = quantiles(mix["new_tokens"], n)
    new = new[np.random.default_rng(0x5EED).permutation(n)]  # fixed pairing
    if "max_total" in mix:
        new = np.minimum(new, mix["max_total"] - prompt_len)
    order = np.random.default_rng(0x0DE2).permutation(n)     # fixed order
    return [(int(prompt_len[i]), int(new[i])) for i in order]


def client_sequences(mix: Dict[str, Any], seed: int, vocab: int
                     ) -> List[List[Dict[str, Any]]]:
    """The pool dealt out to ``clients`` closed-loop callers in turn; which
    client holds which hand, and the token ids, come from the seed. A client
    that runs through its hand starts it again."""
    sizes = request_sizes(mix)
    c = int(mix["clients"])
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    low = int(mix.get("min_token_id", 1))
    hands = []
    for h in rng.permutation(c):
        hands.append([{"prompt": rng.integers(low, vocab, p, dtype=np.int32),
                       "max_new_tokens": n} for p, n in sizes[h::c]])
    return hands
