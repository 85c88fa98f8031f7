"""Plain reference for the ``sdar`` family: SDAR-30B-A3B's layers over whole
sequences in straightforward jax.numpy, float32 at ``highest`` matmul
precision, and the replay of the block diffusion schedule that the program
served. No cache, no paging, no batching, no kernels, nothing of the
program.

A layer is pre-norm, as in Qwen3-MoE::

    h = RMSNorm(x)
    q = rot(RMSNorm_head(W_q h)) (Hq, hd)    k = rot(RMSNorm_head(W_k h)) (Hkv, hd)    v = W_v h
    o_t^a = softmax_j(q_t^a . k_j^c / sqrt hd ; j visible from t) . v_j^c      c = a // (Hq / Hkv)
    x = x + W_o concat_a(o_t^a)
    n = RMSNorm(x);  s = softmax(W_r n) (float32);  E = top-k of s;  w_e = s_e / sum_E s
    x = x + sum_E w_e W_down,e(silu(W_gate,e n) * W_up,e n)

with the rotation's pairs ``(x[i], x[i + hd/2])`` and the logits ``W_head
RMSNorm(x)``. Position ``t`` sees ``j`` iff ``j // B <= t // B``
(block-causal).

THE REPLAY. A served request (prompt of length L, tokens, and the pass of
its block at which each token was unmasked) is rebuilt as ONE sequence: the
clean sequence (prompt and tokens, block-causal, as prefill and the store
passes left it in the pool), followed by one COPY of a block for every
denoising pass, holding the block as the program had it at that pass (the
prompt's tail where fixed, the tokens unmasked at earlier passes, the mask
token elsewhere). A copy sees the clean positions of the blocks before its
own and, bidirectionally, its own B positions, at its block's positions;
nothing sees a copy. The head is read at the copies. A truncated last
block is replayed while the passes before the one replayed unmasked only
tokens the result kept.

The comparison, at every replayed pass: ``served_logit_gap``, the
reference's best logit (the mask's row excluded) at the position the
program unmasked less its logit of the served token; ``served_order_gap``,
the reference's best log-confidence ``logits[x0] - logsumexp(logits)`` over
the still-masked positions less its log-confidence at the position the
program unmasked. Widest over all passes.

Every departure from the published description is the configuration's
``assumed``. The weights are the values the program holds (drawn from the
seed in the served type, a layer at a time) widened to float32 a layer at a
time.

Stand-ins (``control=``): ``"float8"`` and ``"int8"`` round every matmul
operand to that type's levels (per-tensor absmax scale); the router is not
rounded. The faults are planted one in each part the block mechanism and
the layer add: ``"causal_in_block"`` (a position sees its own block
causally, in prefill, copies and stored blocks), ``"kv_from_masked_pass"``
(a stored block's rows come from its last masked pass: the position that
pass unmasked is the mask token in the clean sequence),
``"prompt_tail_masked"`` (a first block's prompt tail is masked in its
copies, and a candidate), ``"wrong_group"`` (query head ``a`` reads
key/value head ``a % Hkv``), ``"no_qk_norm"`` and ``"no_renorm"``. A
stand-in's pick at a pass (its most confident position among ITS masked
ones, and its best token there) is judged by the reference in the served
pick's place.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.brumby import rope_half
from reference.longcat import _rounding, rms_norm, swiglu
from reference.seeded import Leaf, _make_leaves, seed_key

CONTROLS = ("float8", "int8")
FAULTS = ("causal_in_block", "kv_from_masked_pass", "prompt_tail_masked",
          "wrong_group", "no_qk_norm", "no_renorm")
# a stand-in's pick of a position the reference holds fixed has no
# confidence to compare: its order gap reads this
NOT_A_CANDIDATE = 1000.0
# positions of the head's reads a call, a request at a time
_HEAD_CHUNK = 512


class Static(NamedTuple):
    """What the jitted functions need of the configuration, hashable."""

    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    top_k: int
    renorm: bool


def _static(cfg: Dict[str, Any]) -> Static:
    return Static(int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
                  float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                  int(cfg["num_experts_per_tok"]),
                  bool(cfg["norm_topk_prob"]))


def block_of(cfg: Dict[str, Any]) -> int:
    return int(cfg["block_length"])


def mask_of(cfg: Dict[str, Any]) -> int:
    return int(cfg["mask_token_id"])


# ----------------------------------------------------------------- weights


def layer_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's parameter tree (the program's names). ``cfg["init"]``
    gives each matrix's gain: sigma = gain / sqrt(fan_in); ``qk_norm`` the
    value of the q and k norms' gains (set by :func:`make_group`)."""
    d, w, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    gains = cfg.get("init", {})

    def mat(name, *shape):
        return Leaf("normal", shape,
                    gains.get(name, 1.0) / math.sqrt(shape[-2]))

    return {"attn": {"norm": Leaf("ones", (d,)),
                     "W_q": mat("W_q", d, hq * hd),
                     "W_k": mat("W_k", d, hkv * hd),
                     "W_v": mat("W_v", d, hkv * hd),
                     "q_norm": Leaf("ones", (hd,)),
                     "k_norm": Leaf("ones", (hd,)),
                     "W_o": mat("W_o", hq * hd, d)},
            "moe": {"norm": Leaf("ones", (d,)),
                    "router": mat("router", d, e),
                    "Wg": mat("expert_Wg", e, d, w),
                    "Wu": mat("expert_Wu", e, d, w),
                    "Wd": mat("expert_Wd", e, w, d)}}


def outer_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    gains = cfg.get("init", {})
    return {"embed": Leaf("normal", (v, d), gains.get("embed_sigma", 1.0)),
            "final_norm": Leaf("ones", (d,)),
            "head": Leaf("normal", (d, v),
                         gains.get("head", 1.0) / math.sqrt(d))}


def make_group(cfg: Dict[str, Any], seed: int, group: int, dtype) -> Any:
    """The weights of one group, drawn from the seed in ONE jitted call:
    group -1 is what lies outside the layers (embedding, final norm, head),
    group ``i >= 0`` is layer ``i``. The program and the reference both draw
    a group at a time, with this function, and so hold the same values."""
    spec = outer_spec(cfg) if group < 0 else layer_spec(cfg)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, Leaf))
    kinds = tuple((lf.kind, lf.shape, lf.sigma, lf.center) for lf in leaves)
    key = jax.random.fold_in(seed_key(seed), group + 1)
    tree = treedef.unflatten(_make_leaves(key, kinds, jnp.dtype(dtype).name))
    if group >= 0:
        gain = float(cfg.get("init", {}).get("qk_norm", 1.0))
        for name in ("q_norm", "k_norm"):
            tree["attn"][name] = jnp.full((cfg["head_dim"],), gain,
                                          jnp.dtype(dtype))
    return tree


def make_weights(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole tree (what the program holds)."""
    return {**make_group(cfg, seed, -1, dtype),
            "layers": [make_group(cfg, seed, i, dtype)
                       for i in range(cfg["num_hidden_layers"])]}


# --------------------------------------------------------------- the layers


def heads_of(st: Static, fault=None) -> np.ndarray:
    """For each query head, the key/value head it reads."""
    a = np.arange(st.heads)
    if fault == "wrong_group":
        return a % st.kv_heads
    return a // (st.heads // st.kv_heads)


def visible(qs, ks, fault=None):
    """(queries, keys) bool from the positions' attributes ``(pos, blk,
    grp, real)``: a clean key (``grp`` -1) is seen from a later block, and
    from its own block by a clean query; a copy's key by its own copy only;
    padding by nobody. ``causal_in_block``: within a block, no key past the
    query's position."""
    qpos, qblk, qgrp, _ = (a[:, None] for a in qs)
    kpos, kblk, kgrp, kreal = (a[None, :] for a in ks)
    same = kblk == qblk
    ok = (kgrp < 0) & ((kblk < qblk) | (same & (qgrp < 0)))
    ok = ok | ((kgrp >= 0) & (kgrp == qgrp))
    if fault == "causal_in_block":
        ok = ok & (~same | (kpos <= qpos))
    return ok & kreal


def attention(w, x, seq, st: Static, q, fault, block: int = 512):
    """x (T, d) normalised, ``seq`` the positions' attributes ``(pos, blk,
    grp, real)`` (T,) each. Scores of a block of queries at a time."""
    t = x.shape[0]
    hq, hkv, hd = st.heads, st.kv_heads, st.head_dim
    pos = seq[0]
    qh = (q(x) @ q(w["W_q"])).reshape(t, hq, hd)
    kh = (q(x) @ q(w["W_k"])).reshape(t, hkv, hd)
    if fault != "no_qk_norm":
        qh = rms_norm(qh, w["q_norm"], st.eps)
        kh = rms_norm(kh, w["k_norm"], st.eps)
    qh, kh = rope_half(qh, pos, st.theta), rope_half(kh, pos, st.theta)
    read = heads_of(st, fault)
    k = kh[:, read]                                          # (T, Hq, hd)
    v = (q(x) @ q(w["W_v"])).reshape(t, hkv, hd)[:, read]
    outs = []
    for s0 in range(0, t, block):
        rows = slice(s0, s0 + block)
        s = jnp.einsum("qhd,khd->hqk", q(qh[rows]), q(k)) / math.sqrt(hd)
        ok = visible(tuple(a[rows] for a in seq), seq, fault)
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", q(p), q(v)))
    o = jnp.concatenate(outs, axis=0).reshape(t, hq * hd)
    return q(o) @ q(w["W_o"])


def route(w, u, st: Static, fault=None):
    """(T, d) -> the chosen experts (T, k) and their weights (T, k)."""
    s = jax.nn.softmax(u @ w["router"].astype(jnp.float32), axis=-1)
    weight, chosen = jax.lax.top_k(s, st.top_k)
    if st.renorm and fault != "no_renorm":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight


def moe(w, u, st: Static, q, fault):
    """Every expert over every token with the router's weight (nought where
    not chosen), one after another."""
    chosen, weight = route(w, u, st, fault)   # the router is never rounded

    def one(y, ew):
        e, wg, wu, wd = ew
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(u, wg, wu, wd, q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(w["Wg"].shape[0]), w["Wg"], w["Wu"], w["Wd"]))
    return y


def layer(w, x, seq, st: Static, q=lambda x: x, fault=None):
    a, m = w["attn"], w["moe"]
    x = x + attention(a, rms_norm(x, a["norm"], st.eps), seq, st, q, fault)
    return x + moe(m, rms_norm(x, m["norm"], st.eps), st, q, fault)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_over(w, xs, seqs, st, control):
    """One layer over each sequence in turn (xs: (R, T, d) float32, seqs:
    four (R, T) arrays), its weights widened to float32 here, for this call
    alone."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    fault = control if control in FAULTS else None
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda xs_: layer(w, xs_[0], xs_[1:], st, _rounding(control),
                              fault), (xs,) + tuple(seqs))


def _forward(cfg: Dict[str, Any], seed: int, ids, seqs, control, weights):
    """The residual after the last layer (R, T, d) float32, and what lies
    outside the layers (final norm and head, in the served type)."""
    dtype = jnp.dtype(cfg["param_dtype"])
    st = _static(cfg)

    def group(i):
        if weights is None:
            return make_group(cfg, seed, i, dtype)
        return (weights["layers"][i] if i >= 0 else
                {k: weights[k] for k in ("embed", "final_norm", "head")})

    outer = group(-1)
    xs = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)   # (R, T, d)
    seqs = tuple(jnp.asarray(a) for a in seqs)
    for i in range(cfg["num_hidden_layers"]):
        xs = _layer_over(group(i), xs, seqs, st, control)
    return {k: outer[k] for k in ("final_norm", "head")}, xs


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _head_reads(outer, xs, at, tok_a, tok_b, eps, control, mask_id):
    """What the comparison needs of the logits at the positions ``at``
    (R, K), a request and a chunk of positions at a time: the best logit
    with the mask's row excluded, the log-sum-exp, the logits of ``tok_a``
    and ``tok_b`` and the best token (R, K) each."""
    outer = jax.tree.map(lambda a: a.astype(jnp.float32), outer)
    q = _rounding(control)

    def chunk(xat):
        x, a, ta, tb = xat
        logits = q(rms_norm(x[a], outer["final_norm"], eps)) @ q(
            outer["head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.where(jnp.arange(logits.shape[-1])[None, :] == mask_id,
                         -jnp.inf, logits)
        got = lambda t: jnp.take_along_axis(  # noqa: E731
            logits, t[:, None], axis=-1)[:, 0]
        return (jnp.max(pick, axis=-1), lse, got(ta), got(tb),
                jnp.argmax(pick, axis=-1))

    def one(xat):
        x, a, ta, tb = xat
        n = a.shape[0] // _HEAD_CHUNK
        parts = jax.lax.map(
            lambda c: chunk((x,) + c),
            tuple(z.reshape(n, _HEAD_CHUNK) for z in (a, ta, tb)))
        return tuple(p.reshape(-1) for p in parts)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (xs, at, tok_a, tok_b))


# ------------------------------------------------------------- the replay


class Pass(NamedTuple):
    """One replayed denoising pass of one block."""

    block: int            # the block's index: positions block * B ...
    state: np.ndarray     # (B,) the block as the program had it
    masked: np.ndarray    # (B,) bool, the candidates
    chosen: int           # the position the program unmasked
    token: int            # and its token


def replay(prompt, tokens, passes, block: int, mask_id: int,
           fault=None):
    """The clean sequence and the replayed passes of one served request.
    ``passes``: the pass of its block at which each token was unmasked.
    ``kv_from_masked_pass`` changes the clean sequence (the position a
    block's last pass unmasked is masked); ``prompt_tail_masked`` the
    first block's states (its fixed tail masked and a candidate)."""
    prompt = np.asarray(prompt, np.int64)
    tokens = np.asarray(tokens, np.int64)
    passes = np.asarray(passes, np.int64)
    plen, n = len(prompt), len(tokens)
    clean = np.concatenate([prompt, tokens])
    out: List[Pass] = []
    for k in range(plen // block, -(-(plen + n) // block)):
        b0 = k * block
        fixed = np.arange(b0, b0 + block) < plen
        when = {o: int(passes[b0 + o - plen]) for o in range(block)
                if plen <= b0 + o < plen + n}
        n_masked = int(block - fixed.sum())
        for p in range(n_masked):
            if not set(range(p)) <= set(when.values()):
                break
            chosen = [o for o, q in when.items() if q == p]
            if not chosen:
                break        # this pass unmasked a token the result cut
            done = np.array([o in when and when[o] < p for o in range(block)])
            state = np.where(fixed | done,
                             np.resize(clean[b0:b0 + block], block), mask_id)
            masked = ~(fixed | done)
            if fault == "prompt_tail_masked":
                state = np.where(fixed, mask_id, state)
                masked = masked | fixed
            out.append(Pass(k, state, masked, chosen[0],
                            int(clean[b0 + chosen[0]])))
        if fault == "kv_from_masked_pass" and n_masked:
            last = [o for o, q in when.items() if q == n_masked - 1]
            if last:
                clean = clean.copy()
                clean[b0 + last[0]] = mask_id
    return clean, out


def _sequence(clean, out: List[Pass], block: int, length: int):
    """One request's rebuilt sequence, padded to ``length``: ids and the
    positions' attributes (pos, blk, grp, real)."""
    ids = np.zeros((length,), np.int32)
    pos, blk = np.zeros((length,), np.int32), np.zeros((length,), np.int32)
    grp = np.full((length,), -1, np.int32)
    real = np.zeros((length,), bool)
    c = len(clean)
    ids[:c], pos[:c], blk[:c] = clean, np.arange(c), np.arange(c) // block
    real[:c + len(out) * block] = True
    for g, ps in enumerate(out):
        s = slice(c + g * block, c + (g + 1) * block)
        ids[s] = ps.state
        pos[s] = ps.block * block + np.arange(block)
        blk[s], grp[s] = ps.block, g
    return ids, (pos, blk, grp, real)


def served_gaps(cfg: Dict[str, Any], seed: int, sample: List[Dict[str, Any]],
                *, control=None, weights=None) -> Dict[str, Any]:
    """``sample``: requests as {"prompt": ids, "tokens": served ids,
    "unmask_pass": the pass of each}. Replays every request's passes
    through the reference (and, with ``control``, through the stand-in),
    all requests padded to one length. Returns the widest
    ``served_logit_gap`` and ``served_order_gap`` over the passes, the
    stand-in's (``control_*``), and how many passes were read."""
    block, mask_id = block_of(cfg), mask_of(cfg)
    sample = [r for r in sample if len(r["tokens"])]
    empty = {"served_logit_gap": 0.0, "served_order_gap": 0.0,
             "control_logit_gap": 0.0, "control_order_gap": 0.0,
             "passes_read": 0, "distinct_tokens": 0,
             "order_margin_min": None, "order_margin_median": None}
    if not sample:
        return empty
    fault = control if control in FAULTS else None
    plays = [replay(r["prompt"], r["tokens"], r["unmask_pass"], block,
                    mask_id) for r in sample]
    if not sum(len(out) for _, out in plays):
        return empty
    length = max(len(c) + len(out) * block for c, out in plays)
    reads = max(len(out) for _, out in plays) * block
    length = -(-length // 128) * 128
    reads = -(-reads // _HEAD_CHUNK) * _HEAD_CHUNK
    r_n = len(plays)

    def batch(plays_):
        ids = np.zeros((r_n, length), np.int32)
        seqs = tuple(np.zeros((r_n, length), t)
                     for t in (np.int32, np.int32, np.int32, bool))
        for i, (clean, out) in enumerate(plays_):
            ids[i], attrs = _sequence(clean, out, block, length)
            for s, a in zip(seqs, attrs):
                s[i] = a
        return ids, seqs

    at = np.zeros((r_n, reads), np.int32)
    served = np.zeros((r_n, reads), np.int32)
    for i, (clean, out) in enumerate(plays):
        n = len(out) * block
        at[i, :n] = len(clean) + np.arange(n)
        for g, ps in enumerate(out):
            served[i, g * block + ps.chosen] = ps.token
    eps = float(cfg["rms_norm_eps"])
    ids, seqs = batch(plays)
    outer, xs = _forward(cfg, seed, ids, seqs, None, weights)
    low_first = np.zeros_like(served)
    if control is not None:
        stand = [replay(r["prompt"], r["tokens"], r["unmask_pass"], block,
                        mask_id, fault) for r in sample]
        ids_c, seqs_c = batch(stand)
        _, low = _forward(cfg, seed, ids_c, seqs_c, control, weights)
        l_best, l_lse, _, _, l_first = (np.asarray(a) for a in _head_reads(
            outer, low, jnp.asarray(at), jnp.asarray(served),
            jnp.asarray(served), eps, control, mask_id))
        del low
        low_conf = l_best - l_lse
        low_first = l_first.astype(np.int32)
    best, lse, got, got_low, _ = (np.asarray(a) for a in _head_reads(
        outer, xs, jnp.asarray(at), jnp.asarray(served),
        jnp.asarray(low_first), eps, None, mask_id))
    conf = best - lse
    logit_gap, order_gap, margins, tokens = [], [], [], set()
    c_logit, c_order = [], []
    for i, (_, out) in enumerate(plays):
        for g, ps in enumerate(out):
            at_g = slice(g * block, (g + 1) * block)
            cf = conf[i, at_g]
            top = np.max(cf[ps.masked])
            logit_gap.append(best[i, g * block + ps.chosen]
                             - got[i, g * block + ps.chosen])
            order_gap.append(top - cf[ps.chosen])
            tokens.add(ps.token)
            if ps.masked.sum() > 1:
                ranked = np.sort(cf[ps.masked])
                margins.append(ranked[-1] - ranked[-2])
            if control is not None:
                mine = stand[i][1][g].masked
                pick = int(np.argmax(np.where(mine, low_conf[i, at_g],
                                              -np.inf)))
                c_logit.append(best[i, g * block + pick]
                               - got_low[i, g * block + pick])
                c_order.append(top - cf[pick] if ps.masked[pick]
                               else NOT_A_CANDIDATE)
    return {"served_logit_gap": float(np.max(logit_gap)),
            "served_order_gap": float(np.max(order_gap)),
            "control_logit_gap": float(np.max(c_logit)) if c_logit else 0.0,
            "control_order_gap": float(np.max(c_order)) if c_order else 0.0,
            "passes_read": len(logit_gap), "distinct_tokens": len(tokens),
            "order_margin_min": float(np.min(margins)) if margins else None,
            "order_margin_median": (float(np.median(margins)) if margins
                                    else None)}


def prefill_rows(cfg: Dict[str, Any], weights, ids, whole: int, *,
                 fault=None):
    """Every layer's keys (rotated) and values of ONE prompt's clean
    sequence, block-causal over its first ``whole`` positions: (L, 2, T,
    Hkv * hd) float32 (the CPU tests' view of what prefill caches)."""
    block = block_of(cfg)
    t = len(ids)
    pos = np.arange(t, dtype=np.int32)
    seq = tuple(jnp.asarray(a) for a in (pos, pos // block,
                                         np.full((t,), -1, np.int32),
                                         pos < whole))
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    rows = []
    for lw in weights["layers"]:
        kv, x = _rows_and_layer(lw, x, seq, _static(cfg), fault)
        rows.append(kv)
    return jnp.stack(rows)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _rows_and_layer(w, x, seq, st, fault):
    """One layer's keys (rotated) and values of x (T, d), and its output."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    t = x.shape[0]
    with jax.default_matmul_precision("highest"):
        a = w["attn"]
        h = rms_norm(x, a["norm"], st.eps)
        k = rms_norm((h @ a["W_k"]).reshape(t, st.kv_heads, -1),
                     a["k_norm"], st.eps)
        k = rope_half(k, seq[0], st.theta)
        return (jnp.stack([k.reshape(t, -1), h @ a["W_v"]]),
                layer(w, x, seq, st, fault=fault))
