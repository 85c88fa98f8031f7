"""Plain reference for the ``bert`` family: BERT's encoder with the MLM head,
its loss and gradients, and Adam, in straightforward jax.numpy and float32
at ``highest`` matmul precision. No kernels, no cache, nothing of the
program: it makes its own weights and inputs from the seed.

Follows google-research/bert ``modeling.py``: post-LN blocks, the tanh form
of GELU (that file's ``gelu``), tied output embedding with a bias, learned
positions and token types. Departures, each the program's own and followed
here so that the two compute the same function:
  * the MLM head runs over every position and the loss weights the masked
    ones (the source gathers the masked positions first): same loss;
  * dropout, at the configuration's two rates, falls where the program puts
    it and nowhere else: on the attention probabilities after the softmax,
    and on the feed-forward's hidden activations after GELU (the source
    also drops the embeddings and each sub-layer's output). The masks are a
    function of the seed that is part of what the configuration computes:
    ``dropout_draws`` below re-derives them with its own code from the
    model's seed (the chain of jax.random keys of ``BertModel``; the
    attention mask is a hash of seed, batch*head, row and column), so the
    reference follows the same masks and takes nothing the program made;
  * parameters AND Adam's two moments are stored in the configuration's
    ``param_dtype``; the arithmetic of a step is float32 and each store
    rounds (``ops/pallas_updater.py`` does the same). No weight decay, no
    warm-up: the configuration states plain Adam at a fixed rate.

The control (``quant="int8"``) is this same reference with every matmul
operand rounded to int8 levels (per-tensor absmax scale, straight-through
gradient): the nearest precision below bfloat16, and the step that would
tempt a later PR.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.seeded import Leaf, leaf_norms, make_weights  # noqa: F401


# ------------------------------------------------------------- the shapes


def tree_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree. Names follow the BERT checkpoint layout, which
    is also the program's (models/bert.py), so the program takes the tree
    as it is."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]

    def ln():
        return {"ln_gamma": Leaf("ones", (h,)), "ln_beta": Leaf("zeros", (h,))}

    def dense(a, b, wn, bn):
        return {wn: Leaf("normal", (a, b)), bn: Leaf("zeros", (b,))}

    block = lambda: {  # noqa: E731
        "attn": {**dense(h, h, "Wq", "bq"), **dense(h, h, "Wk", "bk"),
                 **dense(h, h, "Wv", "bv"), **dense(h, h, "Wo", "bo"), **ln()},
        "ffn": {**dense(h, i, "W1", "b1"), **dense(i, h, "W2", "b2"), **ln()},
    }
    return {
        "embeddings": {"word": Leaf("normal", (v, h)),
                       "position": Leaf("normal",
                                        (cfg["max_position_embeddings"], h)),
                       "token_type": Leaf("normal",
                                          (cfg["type_vocab_size"], h)),
                       **ln()},
        "encoder": [block() for _ in range(cfg["num_hidden_layers"])],
        "pooler": dense(h, h, "W", "b"),
        "classifier": dense(h, cfg.get("num_labels", 2), "W", "b"),
        "mlm": {**dense(h, h, "W", "b"), **ln(),
                "bias": Leaf("zeros", (v,))},
    }


def make_feed(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int
              ) -> Dict[str, np.ndarray]:
    """One MLM batch from the seed: every row differs. 10% of the positions
    are padding, 15% carry a label (as chip_smoke.phase_bert made it)."""
    rng = np.random.default_rng([int(seed), 0xBE27])
    b, t, v = mix["batch"], mix["seq"], cfg["vocab_size"]
    return {
        "ids": rng.integers(0, v, (b, t), dtype=np.int32),
        "segments": np.zeros((b, t), np.int32),
        "mask": (rng.random((b, t)) > mix.get("pad_share", 0.1)
                 ).astype(np.int32),
        "mlm_labels": rng.integers(0, v, (b, t), dtype=np.int32),
        "mlm_mask": (rng.random((b, t)) < mix.get("label_share", 0.15)
                     ).astype(np.float32),
    }


# -------------------------------------------------------------- the model


def _round_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _round_fp8(x, dtype=jnp.float8_e4m3fn, top=448.0):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(x.dtype) * scale


# per control: how a matmul's operands are rounded going forward, and how the
# incoming gradient is rounded going backward (per-tensor absmax scale; the
# usual 8-bit training recipe: e4m3 forward, e5m2 backward)
ROUNDINGS = {
    "int8": (_round_int8, _round_int8),
    "fp8": (_round_fp8, lambda g: _round_fp8(g, jnp.float8_e5m2, 57344.0)),
}


def _operand(quant: Optional[str]):
    """Rounding of an attention matmul's operand; the gradient passes
    straight through."""
    if quant is None:
        return lambda x: x
    fwd = ROUNDINGS[quant][0]
    return lambda x: x + jax.lax.stop_gradient(fwd(x) - x)


def _dense(quant: Optional[str]):
    """``x @ w`` of a dense layer. Under a control both operands are rounded
    going forward and the incoming gradient going backward, as a step that
    ran its matmuls in that precision would."""
    if quant is None:
        return lambda x, w: x @ w
    fwd, bwd = ROUNDINGS[quant]

    @jax.custom_vjp
    def mm(x, w):
        return fwd(x) @ fwd(w)

    def mm_fwd(x, w):
        xq, wq = fwd(x), fwd(w)
        return xq @ wq, (xq, wq)

    def mm_bwd(res, g):
        xq, wq = res
        gq = bwd(g)
        lead = tuple(range(xq.ndim - 1))
        return gq @ wq.T, jnp.tensordot(xq, gq, axes=(lead, lead))

    mm.defvjp(mm_fwd, mm_bwd)
    return mm


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


# -------------------------------------------------------------- dropout


def model_seed(seed: int) -> int:
    """The whole number the model's own key chain starts from (the program
    adds 1 to it and jax.random.key takes 31 bits)."""
    return int(seed) & 0x3FFFFFFF


def step_keys(seed: int, steps: int, layers: int):
    """(steps, 2 * layers) keys: for step i (counted from 0, one step to a
    call), layer l draws its attention mask from key [i, 2l] and its
    feed-forward mask from key [i, 2l + 1]. The chain is BertModel's: a
    model key made from its seed + 1, split once per call, the call's half
    folded with the step's number, then split over the layers."""
    key = jax.random.key(model_seed(seed) + 1)
    rows = []
    for i in range(steps):
        key, sub = jax.random.split(key)
        rows.append(jax.random.split(jax.random.fold_in(sub, i), 2 * layers))
    return jnp.stack(rows)


def attention_keep(key, rows0, n: int, heads: int, t: int, rate: float):
    """(n, heads, t, t) keep mask of the attention probabilities for batch
    rows rows0 .. rows0 + n: a murmur-style mix, in wrapping int32, of the
    key's last word, batch*heads + head, row and column, thresholded on 24
    bits (ops/pallas_attention.py documents it as the kernel's mask)."""
    i32 = jnp.int32
    seed = jax.random.key_data(key).reshape(-1)[-1].astype(i32)
    bh = ((rows0 + jnp.arange(n, dtype=i32))[:, None] * i32(heads)
          + jnp.arange(heads, dtype=i32)[None, :])[:, :, None, None]
    row = jnp.arange(t, dtype=i32)[None, None, :, None]
    col = jnp.arange(t, dtype=i32)[None, None, None, :]
    h = seed + bh * i32(7919) + row * i32(1103515245) + col * i32(1299709)
    h = h ^ jax.lax.shift_right_logical(h, i32(13))
    h = h * i32(1274126177)
    h = h ^ jax.lax.shift_right_logical(h, i32(16))
    u = (h & i32(0xFFFFFF)).astype(jnp.float32) * (1.0 / (1 << 24))
    return u >= rate


def hidden_keep(key, shape, rate: float):
    """Keep mask of the feed-forward's hidden activations, whole batch."""
    return jax.random.bernoulli(key, 1 - rate, shape)


def encoder(p, ids, segments, mask, cfg, quant=None, drop=None):
    """(N, T) ids -> (N, T, H), float32. ``drop``: None, or this step's
    masks for these rows: {"keys": (2L,) keys, "rows0": first batch row,
    "hidden": (L, N, T, I) keep masks}."""
    q8, mm = _operand(quant), _dense(quant)
    rate_a = cfg.get("attention_probs_dropout_prob", 0.0)
    rate_h = cfg.get("hidden_dropout_prob", 0.0)
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    n, t = ids.shape
    emb = p["embeddings"]
    x = emb["word"][ids] + emb["position"][:t][None] + emb["token_type"][segments]
    x = layer_norm(x, emb["ln_gamma"], emb["ln_beta"], eps)
    dh = x.shape[-1] // heads
    keep = (mask > 0)[:, None, None, :]

    def split(a):
        return a.reshape(n, t, heads, dh).transpose(0, 2, 1, 3)

    for li, blk in enumerate(p["encoder"]):
        a = blk["attn"]
        q = split(mm(x, a["Wq"]) + a["bq"])
        k = split(mm(x, a["Wk"]) + a["bk"])
        v = split(mm(x, a["Wv"]) + a["bv"])
        s = jnp.einsum("nhqd,nhkd->nhqk", q8(q), q8(k)) / np.sqrt(dh)
        s = jnp.where(keep, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        if drop is not None and rate_a > 0:
            kept = attention_keep(drop["keys"][2 * li], drop["rows0"], n,
                                  heads, t, rate_a)
            w = jnp.where(kept, w / (1.0 - rate_a), 0.0)
        o = jnp.einsum("nhqk,nhkd->nhqd", q8(w), q8(v))
        o = o.transpose(0, 2, 1, 3).reshape(n, t, heads * dh)
        x = layer_norm(x + mm(o, a["Wo"]) + a["bo"],
                       a["ln_gamma"], a["ln_beta"], eps)
        f = blk["ffn"]
        hdn = gelu_tanh(mm(x, f["W1"]) + f["b1"])
        if drop is not None and rate_h > 0:
            hdn = jnp.where(drop["hidden"][li], hdn / (1.0 - rate_h), 0.0)
        x = layer_norm(x + mm(hdn, f["W2"]) + f["b2"],
                       f["ln_gamma"], f["ln_beta"], eps)
    return x


def mlm_nll_sum(p, feed, cfg, quant=None, drop=None):
    """Sum over the labelled positions of -log p(label)."""
    mm = _dense(quant)
    seq = encoder(p, feed["ids"], feed["segments"], feed["mask"], cfg, quant,
                  drop)
    m = p["mlm"]
    h = gelu_tanh(mm(seq, m["W"]) + m["b"])
    h = layer_norm(h, m["ln_gamma"], m["ln_beta"], cfg["layer_norm_eps"])
    logits = mm(h, p["embeddings"]["word"].T) + m["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, feed["mlm_labels"][..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * feed["mlm_mask"])


# --------------------------------------------------- loss, gradient, Adam


def loss_and_grads(p32, feed, cfg, *, block_rows: int, quant=None,
                   rows=None, keys=None):
    """Mean MLM loss over the labelled positions of ``rows`` (all, or the
    given slice: the half-batch fault) and its gradient, in blocks of rows
    so that it fits beside nothing else on one chip. ``keys``: this step's
    (2L,) dropout keys, or None for no dropout."""
    n_all, t = feed["ids"].shape
    hidden = None
    if keys is not None and cfg.get("hidden_dropout_prob", 0.0) > 0:
        # drawn for the whole batch, as the program draws it, then cut
        layers = cfg["num_hidden_layers"]
        hidden = jnp.stack([hidden_keep(
            keys[2 * li + 1], (n_all, t, cfg["intermediate_size"]),
            cfg["hidden_dropout_prob"]) for li in range(layers)], axis=1)
    if rows is not None:
        feed = {k: v[rows] for k, v in feed.items()}
        hidden = None if hidden is None else hidden[rows]
    n = feed["ids"].shape[0]
    block_rows = min(block_rows, n)
    assert n % block_rows == 0, (n, block_rows)
    cut = lambda a: a.reshape(  # noqa: E731
        (n // block_rows, block_rows) + a.shape[1:])
    blocks = {"feed": jax.tree.map(cut, feed),
              "rows0": jnp.arange(0, n, block_rows, dtype=jnp.int32)}
    if hidden is not None:
        blocks["hidden"] = cut(hidden)   # (blocks, rows, L, T, I)
    count = jnp.maximum(jnp.sum(feed["mlm_mask"]), 1.0)

    def body(acc, blk):
        drop = None
        if keys is not None:
            drop = {"keys": keys, "rows0": blk["rows0"],
                    "hidden": None if hidden is None
                    else jnp.moveaxis(blk["hidden"], 1, 0)}
        nll, g = jax.value_and_grad(mlm_nll_sum)(p32, blk["feed"], cfg,
                                                 quant, drop)
        return (acc[0] + nll, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p32))
    (nll, g), _ = jax.lax.scan(body, zero, blocks)
    return nll / count, jax.tree.map(lambda a: a / count, g)


def adam_step(p, m, v, g, t, opt):
    """One step on stored-type leaves with float32 arithmetic; ``t`` counts
    from 1. alpha_t = lr * sqrt(1 - b2^t) / (1 - b1^t)."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    tf = jnp.asarray(t, jnp.float32)
    alpha = opt["learning_rate"] * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)

    def leaf(pw, mw, vw, gw):
        f32 = jnp.float32
        m32 = b1 * mw.astype(f32) + (1 - b1) * gw
        v32 = b2 * vw.astype(f32) + (1 - b2) * gw * gw
        new = pw.astype(f32) - alpha * m32 / (jnp.sqrt(v32) + eps)
        return new.astype(pw.dtype), m32.astype(mw.dtype), v32.astype(vw.dtype)

    out = jax.tree.map(leaf, p, m, v, g)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def three_steps(cfg, mix, seed: int, *, quant=None, half_batch=False,
                steps: int = 3, first_moment=None, keep_moment=False
                ) -> Dict[str, Any]:
    """The reference's first steps on the seed's weights and batch: each
    step's loss, the first gradient's norm per leaf, and the norm of each
    leaf's change after the steps. ``first_moment`` is Adam's first moment
    after one step as the program (or a stand-in) holds it: its gradient,
    m / (1 - beta1), is then compared leaf by leaf with the reference's
    (``grad_diff_norms``). ``keep_moment`` returns this run's own."""
    opt = cfg["optimizer"]
    assert opt["kind"] == "Adam", opt
    dtype = jnp.dtype(cfg["param_dtype"])
    spec = tree_spec(cfg)
    with jax.default_matmul_precision("highest"):
        p0 = make_weights(spec, seed, dtype)
        feed = jax.tree.map(jnp.asarray, make_feed(cfg, mix, seed))
        if first_moment is not None:
            first_moment = jax.tree.map(jnp.asarray, first_moment)
        keys = None
        if max(cfg.get("hidden_dropout_prob", 0.0),
               cfg.get("attention_probs_dropout_prob", 0.0)) > 0:
            keys = step_keys(seed, steps, cfg["num_hidden_layers"])
        out = _steps(p0, feed, first_moment, keys, _freeze(cfg),
                     mix.get("reference_block_rows", 8), quant,
                     bool(half_batch), steps, bool(keep_moment))
        return jax.tree.map(np.asarray, out)


def _freeze(cfg):
    keep = ("hidden_size", "intermediate_size", "vocab_size",
            "num_attention_heads", "num_hidden_layers", "layer_norm_eps",
            "hidden_dropout_prob", "attention_probs_dropout_prob")
    return tuple((k, cfg[k]) for k in keep) + (
        ("optimizer", tuple(sorted(cfg["optimizer"].items()))),)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _steps(p0, feed, moment, keys, frozen, block_rows, quant, half_batch,
           steps, keep_moment):
    cfg = dict(frozen)
    opt = dict(cfg["optimizer"])
    rows = slice(0, feed["ids"].shape[0] // 2) if half_batch else None
    zeros = jax.tree.map(jnp.zeros_like, p0)
    theirs = None if moment is None else jax.tree.map(
        lambda a: a.astype(jnp.float32) / (1.0 - opt["beta1"]), moment)

    def body(carry, step):
        t, step_key = step
        p, m, v, m1 = carry
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        loss, g = loss_and_grads(p32, feed, cfg, block_rows=block_rows,
                                 quant=quant, rows=rows, keys=step_key)
        p, m, v = adam_step(p, m, v, g, t, opt)
        if keep_moment:
            m1 = jax.tree.map(lambda new, old: jnp.where(t == 1, new, old),
                              m, m1)
        diff = None if theirs is None else leaf_norms(
            jax.tree.map(jnp.subtract, theirs, g))
        return (p, m, v, m1), (loss, leaf_norms(g), diff)

    (p, _, _, m1), (losses, gnorms, diffs) = jax.lax.scan(
        body, (p0, zeros, zeros, zeros if keep_moment else None),
        (jnp.arange(1, steps + 1), keys))
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
    first = lambda tree: jax.tree.map(lambda a: a[0], tree)  # noqa: E731
    out = {"losses": losses, "grad_norms": first(gnorms),
           "change_norms": change}
    if theirs is not None:
        out["grad_diff_norms"] = first(diffs)
    if keep_moment:
        out["first_moment"] = m1
    return out


# ---------------------------------------------------------- the comparison


def worst_leaf_gap(program, reference, *, skip=None):
    """Largest over the leaves of |program's norm - reference's norm| over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns (gap, name of the leaf)."""
    prog = jax.tree_util.tree_leaves_with_path(program)
    ref = jax.tree.leaves(reference)
    keep = [True] * len(ref) if skip is None else [not s for s in
                                                   jax.tree.leaves(skip)]
    ref_kept = [float(r) for r, k in zip(ref, keep) if k]
    floor = float(np.median(ref_kept)) if ref_kept else 0.0
    worst, where = 0.0, ""
    for (path, pv), rv, k in zip(prog, ref, keep):
        if not k:
            continue
        gap = abs(float(pv) - float(rv)) / max(float(rv), floor, 1e-30)
        if not gap <= worst:  # NaN wins
            worst, where = gap, jax.tree_util.keystr(path)
    return worst, where


def compare(readings: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers compared, from the program's readings (or a stand-in's)
    and the reference's: each a gap, 0 where the two agree."""
    pl, rl = np.asarray(readings["losses"], np.float64), np.asarray(
        ref["losses"], np.float64)
    n = min(len(pl), len(rl))
    loss_gap = float(np.max(np.abs(pl[:n] - rl[:n]) / np.abs(rl[:n])))
    grad_gap, grad_leaf = worst_leaf_gap(readings["grad_norms"],
                                         ref["grad_norms"])
    # leaves whose gradient is nought to rounding in the reference (a key's
    # bias under softmax) move under Adam by round-off alone: left out of
    # the change by a rule on the reference's gradient, not by name
    g = [float(x) for x in jax.tree.leaves(ref["grad_norms"])]
    tiny = jax.tree.map(lambda x: float(x) < 1e-3 * float(np.median(g)),
                        ref["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(
        readings["change_norms"], ref["change_norms"], skip=tiny)
    out = {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
           "change_norm_gap": change_gap, "grad_leaf": grad_leaf,
           "change_leaf": change_leaf,
           "skipped_leaves": int(sum(jax.tree.leaves(tiny)))}
    if "grad_diff_norms" in ref:
        # the norm of the DIFFERENCE of the two first gradients, by the
        # worst leaf: first order in rounding noise, where a gap of norms is
        # second order; against the same floor
        floor = float(np.median(g))
        diffs = jax.tree_util.tree_leaves_with_path(ref["grad_diff_norms"])
        worst = max(((float(d) / max(float(r), floor, 1e-30),
                      jax.tree_util.keystr(path))
                     for (path, d), r in zip(diffs, g)),
                    key=lambda x: (x[0] != x[0], x[0]))
        out["grad_diff"], out["grad_diff_leaf"] = worst
    return out
