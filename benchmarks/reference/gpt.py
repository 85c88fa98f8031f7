"""Plain reference for the ``gpt`` family: a causal decoder's full-sequence
forward in straightforward jax.numpy, float32 at ``highest`` matmul
precision. No cache, no paging, no batching, nothing of the program.

The configuration is openai-community/gpt2's sizes. The BLOCK, though, is
the program's (models/gpt.py), which is not GPT-2's: post-LN residual blocks
with a layer norm after the embeddings and none before the head, where
GPT-2 is pre-LN with a final ``ln_f``. The reference follows the program so
that the two compute the same function; the departure is the program's and
is listed in PERF.md. GELU is the tanh form (``gelu_new``), the output
embedding is tied, no bias on the head.

The control (``dtype="bfloat16"``) is this reference with weights and
activations in bfloat16: the nearest precision below the float32 the
configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference.seeded import Leaf, make_weights  # noqa: F401


def tree_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree (the program's names). ``cfg["init"]`` says how
    the seed's weights are drawn: see the configuration file for why they
    are not all N(0, 0.02)."""
    h, v = cfg["n_embd"], cfg["vocab_size"]
    i = cfg.get("n_inner") or 4 * h
    init = cfg.get("init", {})
    block_sigma = init.get("block_matrix_sigma", 0.02)
    centered = tuple(init.get("centered", ()))

    def ln():
        return {"ln_gamma": Leaf("ones", (h,)), "ln_beta": Leaf("zeros", (h,))}

    def dense(a, b, wn, bn):
        return {wn: Leaf("normal", (a, b), block_sigma, wn in centered),
                bn: Leaf("zeros", (b,))}

    block = lambda: {  # noqa: E731
        "attn": {**dense(h, h, "Wq", "bq"), **dense(h, h, "Wk", "bk"),
                 **dense(h, h, "Wv", "bv"), **dense(h, h, "Wo", "bo"), **ln()},
        "ffn": {**dense(h, i, "W1", "b1"), **dense(i, h, "W2", "b2"), **ln()},
    }
    return {"embeddings": {"word": Leaf("normal", (v, h),
                                        init.get("word_sigma", 0.02)),
                           "position": Leaf("normal", (cfg["n_positions"], h),
                                            init.get("position_sigma", 0.02)),
                           **ln()},
            "blocks": [block() for _ in range(cfg["n_layer"])]}


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _int8(x):
    """Round to the int8 levels of a per-tensor absmax scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def logits_at(p, ids, at, heads: int, eps: float, q=lambda x: x):
    """ids (T,), positions ``at`` (K,) -> logits (K, V) of the causal
    forward over the whole of ``ids``. Padding after the last position read
    changes nothing before it. ``q`` rounds every matmul operand (a
    control); the reference leaves them as they are."""
    t = ids.shape[0]
    emb = p["embeddings"]
    x = emb["word"][ids] + emb["position"][:t]
    x = layer_norm(x, emb["ln_gamma"], emb["ln_beta"], eps)
    dh = x.shape[-1] // heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def split(a):
        return a.reshape(t, heads, dh).transpose(1, 0, 2)

    for blk in p["blocks"]:
        a = blk["attn"]
        xq = q(x)
        qh, k, v = (split(xq @ q(a[w]) + a[b]) for w, b in
                    (("Wq", "bq"), ("Wk", "bk"), ("Wv", "bv")))
        s = jnp.einsum("hqd,hkd->hqk", q(qh), q(k)) / np.sqrt(dh).astype(x.dtype)
        s = jnp.where(causal[None], s, jnp.asarray(-1e30, s.dtype))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,hkd->hqd", q(w), q(v))
        o = o.transpose(1, 0, 2).reshape(t, -1)
        x = layer_norm(x + q(o) @ q(a["Wo"]) + a["bo"], a["ln_gamma"],
                       a["ln_beta"], eps)
        f = blk["ffn"]
        hdn = gelu_tanh(q(x) @ q(f["W1"]) + f["b1"])
        x = layer_norm(x + q(hdn) @ q(f["W2"]) + f["b2"], f["ln_gamma"],
                       f["ln_beta"], eps)
    return q(x[at]) @ q(emb["word"]).T


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gaps(p, ids, at, heads, eps, control, served):
    """Per position read: how far the served token's logit lies below the
    reference's best; the same for the token that the control puts first
    (``"bfloat16"``: weights and activations in that type; ``"int8"``: every
    matmul operand rounded to int8 levels); and the reference's own margin
    between its two best."""
    with jax.default_matmul_precision("highest"):
        ref = logits_at(p, ids, at, heads, eps)
    top2 = jax.lax.top_k(ref, 2)[0]
    best, margin = top2[:, 0], top2[:, 0] - top2[:, 1]
    gap = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    if control is None:
        return gap, jnp.zeros_like(gap), margin
    if control == "int8":
        with jax.default_matmul_precision("highest"):
            low = logits_at(p, ids, at, heads, eps, _int8)
    else:
        low = logits_at(jax.tree.map(lambda a: a.astype(control), p), ids,
                        at, heads, eps)
    first = jnp.argmax(low, axis=-1)
    cgap = best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
    return gap, cgap, margin


def served_gaps(cfg: Dict[str, Any], seed: int, sample: List[Dict[str, Any]],
                *, max_new: int, control=None) -> Dict[str, Any]:
    """``sample``: requests as {"prompt": ids, "tokens": served ids}. Runs
    the reference once over each prompt with its served tokens. Returns the
    widest gap of a served token, how many served tokens were read, and
    (``control``: "bfloat16" or "int8") the widest gap of the control's
    first tokens."""
    dtype = jnp.dtype(cfg["param_dtype"])
    p = make_weights(tree_spec(cfg), seed, dtype)
    t_max = cfg["n_positions"]
    widest, cwidest, read, margins, seen = 0.0, 0.0, 0, [], set()
    for req in sample:
        prompt = np.asarray(req["prompt"], np.int32)
        toks = np.asarray(req["tokens"], np.int32)
        n = len(toks)
        if n == 0:
            continue
        ids = np.zeros((t_max,), np.int32)
        full = np.concatenate([prompt, toks])[:t_max]
        ids[:len(full)] = full
        at = np.full((max_new,), len(prompt) - 1, np.int32)
        at[:n] = len(prompt) - 1 + np.arange(n)
        served = np.zeros((max_new,), np.int32)
        served[:n] = toks
        gap, cgap, margin = _gaps(p, jnp.asarray(ids), jnp.asarray(at),
                          cfg["n_head"], cfg["layer_norm_epsilon"],
                          control, jnp.asarray(served))
        widest = max(widest, float(np.max(np.asarray(gap)[:n])))
        cwidest = max(cwidest, float(np.max(np.asarray(cgap)[:n])))
        read += n
        margins.extend(np.asarray(margin)[:n].tolist())
        seen.update(toks.tolist())
    return {"served_logit_gap": widest, "control_logit_gap": cwidest,
            "tokens_read": read, "distinct_tokens": len(seen),
            "top2_margin_min": float(np.min(margins)) if margins else None,
            "top2_margin_median": float(np.median(margins)) if margins
            else None}
