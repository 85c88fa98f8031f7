"""Plain reference for the ``brumby`` family: Brumby-14B-Base's layers,
full-sequence forward in straightforward jax.numpy, float32 at ``highest``
matmul precision. No state, no pool, no batching, no kernels, nothing of the
program.

A layer is pre-norm, ``x += W_o retention(RMSNorm(x)); x += W_down(silu(
W_gate n) * W_up n)`` with ``n = RMSNorm(x)``. Retention, per token ``h``::

    q = rot(RMSNorm_head(W_q h))  (Hq, hd)     k = rot(RMSNorm_head(W_k h))  (Hkv, hd)
    v = W_v h  (Hkv, hd)                        g = log sigmoid(W_g h + b_g)  (Hkv,)
    w[t, j] = ((q_t^a . k_j^c) / sqrt hd)^2 * exp(g_{j+1}^c + ... + g_t^c)   j <= t,  c = a // (Hq / Hkv)
    y_t^a   = sum_j w[t, j] v_j^c / (sum_j w[t, j] + eps)

computed as that QUADRATIC FORM over the whole sequence, a block of queries
at a time. The rotation pairs ``x[i]`` with ``x[i + hd/2]``. Every departure
from the published description is the configuration's ``assumed``. The
weights are the values the program holds (drawn from the seed in the served
type, a layer at a time) widened to float32 a layer at a time.

Stand-ins (``control=``): ``"float8"`` and ``"int8"`` round every matmul
operand of the projections, the feed-forward and the head to that type's
levels (retention's own products stay float32, as a deployment in those
types would keep them); ``"bf16_state"`` computes retention as the
RECURRENCE over a state kept in bfloat16 (``S (hd, hd, dv)`` of the plain
outer products ``k_a k_b v``, and ``z``, rounded after every token): the
precision below the configuration's ``state_dtype``. The faults are planted
one in each part: ``"no_gate"`` (decay 1), ``"no_normaliser"`` (no division),
``"degree_1"`` (``w = (q . k) / sqrt hd`` times the decay), ``"no_prompt_state"``
(a generated token's sums leave the prompt's positions out: a decode that
starts from an empty state) and ``"wrong_group"`` (query head ``a`` reads
key/value head ``a % Hkv``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# what every family's reference rounds and normalises alike
from reference.longcat import _rounding, rms_norm, swiglu
# the final norm, the head and what the comparison reads of the logits, a
# sequence at a time, over a residual of several streams: one stream here
from reference.xing import _head_over, _head_reads
from reference.seeded import Leaf, _make_leaves, seed_key

CONTROLS = ("bf16_state", "float8", "int8")
FAULTS = ("no_gate", "no_normaliser", "degree_1", "no_prompt_state",
          "wrong_group")


class Static(NamedTuple):
    """What the jitted functions need of the configuration, hashable."""

    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    ret_eps: float


def _static(cfg: Dict[str, Any]) -> Static:
    return Static(int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
                  float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                  float(cfg["retention_eps"]))


# ----------------------------------------------------------------- weights


def layer_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's parameter tree (the program's names). ``cfg["init"]``
    gives each matrix's gain: sigma = gain / sqrt(fan_in). ``b_g`` is given
    its value by :func:`make_group`."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
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
                     "W_g": mat("W_g", d, hkv), "b_g": Leaf("zeros", (hkv,)),
                     "q_norm": Leaf("ones", (hd,)),
                     "k_norm": Leaf("ones", (hd,)),
                     "W_o": mat("W_o", hq * hd, d)},
            "ffn": {"norm": Leaf("ones", (d,)), "Wg": mat("Wg", d, f),
                    "Wu": mat("Wu", d, f), "Wd": mat("Wd", f, d)}}


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
        bias = float(cfg.get("init", {}).get("gate_bias", 0.0))
        tree["attn"]["b_g"] = jnp.full(
            (cfg["num_key_value_heads"],), bias, jnp.dtype(dtype))
    return tree


def make_weights(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole tree (what the program holds)."""
    return {**make_group(cfg, seed, -1, dtype),
            "layers": [make_group(cfg, seed, i, dtype)
                       for i in range(cfg["num_hidden_layers"])]}


# --------------------------------------------------------------- the layers


def rope_half(x, pos, theta):
    """Rotate the pairs (x[i], x[i + n/2]) of the last axis by ``pos *
    theta^(-2i/n)``. x: (T, H, n), pos: (T,)."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    lo, hi = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], -1)


def _heads_of(st: Static, fault):
    """For each query head, the key/value head it reads."""
    a = np.arange(st.heads)
    if fault == "wrong_group":
        return a % st.kv_heads
    return a // (st.heads // st.kv_heads)


def quadratic_form(q, k, v, g, plen, st: Static, fault, block: int = 512):
    """q (T, Hq, hd), k, v (T, Hkv, hd), g (T, Hkv), ``plen`` the prompt's
    length (only ``no_prompt_state`` reads it) -> y (T, Hq, hd)."""
    t = q.shape[0]
    read = _heads_of(st, fault)
    k, v, g = k[:, read], v[:, read], g[:, read]          # a row a query head
    if fault == "no_gate":
        g = jnp.zeros_like(g)
    total = jnp.cumsum(g, axis=0).T                             # (Hq, T)
    pos = jnp.arange(t)
    outs = []
    for s0 in range(0, t, block):
        rows = pos[s0:s0 + block]
        s = jnp.einsum("qhd,khd->hqk", q[s0:s0 + block], k) / math.sqrt(
            st.head_dim)
        ok = pos[None, :] <= rows[:, None]
        if fault == "no_prompt_state":
            ok &= ~((rows[:, None] >= plen) & (pos[None, :] < plen))
        fade = jnp.exp(jnp.where(ok[None], total[:, rows, None]
                                 - total[:, None, :], -jnp.inf))
        w = (s if fault == "degree_1" else s * s) * fade
        num = jnp.einsum("hqk,khv->qhv", w, v)
        if fault != "no_normaliser":
            num = num / (jnp.sum(w, axis=-1).T + st.ret_eps)[..., None]
        outs.append(num)
    return jnp.concatenate(outs, axis=0)


def recurrence(q, k, v, g, st: Static, state_dtype):
    """The same function as a recurrence over a state kept in
    ``state_dtype``: ``S[a, b, :] += k_a k_b v / sqrt hd`` after the decay,
    read by ``q_a q_b / sqrt hd``. Returns y (T, Hq, hd)."""
    hkv, hd = st.kv_heads, st.head_dim
    grp = st.heads // hkv
    scale = 1.0 / math.sqrt(math.sqrt(hd))

    def step(carry, x):
        s, z = carry
        qt, kt, vt, gt = x
        lam = jnp.exp(gt)
        kk = jnp.einsum("ha,hb->hab", kt * scale, kt * scale)
        s = (lam[:, None, None, None] * s.astype(jnp.float32)
             + kk[..., None] * vt[:, None, None, :]).astype(state_dtype)
        z = (lam[:, None, None] * z.astype(jnp.float32) + kk).astype(
            state_dtype)
        qg = qt.reshape(hkv, grp, hd) * scale
        qq = jnp.einsum("hga,hgb->hgab", qg, qg)
        num = jnp.einsum("hgab,habv->hgv", qq, s.astype(jnp.float32))
        den = jnp.einsum("hgab,hab->hg", qq, z.astype(jnp.float32))
        return (s, z), (num / (den + st.ret_eps)[..., None]).reshape(
            st.heads, hd)

    init = (jnp.zeros((hkv, hd, hd, hd), state_dtype),
            jnp.zeros((hkv, hd, hd), state_dtype))
    return jax.lax.scan(step, init, (q, k, v, g))[1]


def retention(w, x, plen, st: Static, qz, control):
    """x (T, d) normalised -> (T, d)."""
    t = x.shape[0]
    hq, hkv, hd = st.heads, st.kv_heads, st.head_dim
    pos = jnp.arange(t)
    q = (qz(x) @ qz(w["W_q"])).reshape(t, hq, hd)
    k = (qz(x) @ qz(w["W_k"])).reshape(t, hkv, hd)
    q = rope_half(rms_norm(q, w["q_norm"], st.eps), pos, st.theta)
    k = rope_half(rms_norm(k, w["k_norm"], st.eps), pos, st.theta)
    v = (qz(x) @ qz(w["W_v"])).reshape(t, hkv, hd)
    g = jax.nn.log_sigmoid(qz(x) @ qz(w["W_g"]) + w["b_g"])
    if control == "bf16_state":
        y = recurrence(q, k, v, g, st, jnp.bfloat16)
    else:
        y = quadratic_form(q, k, v, g, plen, st,
                           control if control in FAULTS else None)
    return qz(y.reshape(t, hq * hd)) @ qz(w["W_o"])


def layer(w, x, plen, st: Static, qz=lambda x: x, control=None):
    a, f = w["attn"], w["ffn"]
    x = x + retention(a, rms_norm(x, a["norm"], st.eps), plen, st, qz,
                      control)
    return x + swiglu(rms_norm(x, f["norm"], st.eps), f["Wg"], f["Wu"],
                      f["Wd"], qz)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_over(w, xs, plens, st, control):
    """One layer over each sequence in turn (xs: (B, T, d) float32, plens
    (B,)), its weights widened to float32 here, for this call alone."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda xp: layer(w, xp[0], xp[1], st, _rounding(control),
                             control), (xs, plens))


def _forward(cfg: Dict[str, Any], seed: int, ids, plens, control, weights):
    """The residual after the last layer as ONE stream (B, T, 1, d) float32,
    and what lies outside the layers (final norm and head, in the served
    type)."""
    dtype = jnp.dtype(cfg["param_dtype"])
    st = _static(cfg)

    def group(i):
        if weights is None:
            return make_group(cfg, seed, i, dtype)
        return (weights["layers"][i] if i >= 0 else
                {k: weights[k] for k in ("embed", "final_norm", "head")})

    outer = group(-1)
    xs = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)   # (B, T, d)
    plens = jnp.asarray(plens, jnp.int32)
    for i in range(cfg["num_hidden_layers"]):
        xs = _layer_over(group(i), xs, plens, st, control)
    return {k: outer[k] for k in ("final_norm", "head")}, xs[:, :, None, :]


def logits_at(cfg: Dict[str, Any], seed: int, ids, at, *, control=None,
              weights=None, prompt_lens=None):
    """ids (B, T) int32, positions ``at`` (B, K) -> logits (B, K, V) of the
    causal forward over each whole row of ``ids``. Padding after the last
    position read changes nothing before it. ``weights``: a whole tree to
    use in place of the seed's (the CPU tests); ``prompt_lens`` (B,): where
    each row's prompt ends (``no_prompt_state`` alone reads it)."""
    ids = np.asarray(ids)
    plens = (np.full((ids.shape[0],), ids.shape[1], np.int32)
             if prompt_lens is None else prompt_lens)
    outer, xs = _forward(cfg, seed, ids, plens, control, weights)
    return _head_over(outer, xs, jnp.asarray(at), float(cfg["rms_norm_eps"]),
                      control)


# ---------------------------------------------------------- the comparison


def served_gaps(cfg: Dict[str, Any], seed: int, sample: List[Dict[str, Any]],
                *, max_new: int, max_total: int, control=None,
                weights=None) -> Dict[str, Any]:
    """``sample``: requests as {"prompt": ids, "tokens": served ids}. The
    reference runs once over each prompt with its served tokens, all
    requests padded to ``max_total`` positions and ``max_new`` reads.
    Returns the widest gap by which a served token's logit lies below the
    reference's best, how many served tokens were read, and (``control``)
    the widest such gap of the tokens the stand-in puts first."""
    sample = [r for r in sample if len(r["tokens"])]
    if not sample:
        return {"served_logit_gap": 0.0, "control_logit_gap": 0.0,
                "tokens_read": 0, "distinct_tokens": 0,
                "top2_margin_min": None, "top2_margin_median": None}
    b = len(sample)
    ids = np.zeros((b, max_total), np.int32)
    at = np.zeros((b, max_new), np.int32)
    served = np.zeros((b, max_new), np.int32)
    read = np.zeros((b, max_new), bool)
    plens = np.zeros((b,), np.int32)
    for i, req in enumerate(sample):
        prompt = np.asarray(req["prompt"], np.int32)
        toks = np.asarray(req["tokens"], np.int32)[:max_new]
        full = np.concatenate([prompt, toks])[:max_total]
        ids[i, :len(full)] = full
        n = min(len(toks), max_total - len(prompt) + 1)
        plens[i] = len(prompt)
        at[i] = len(prompt) - 1
        at[i, :n] = len(prompt) - 1 + np.arange(n)
        served[i, :n] = toks[:n]
        read[i, :n] = True
    eps = float(cfg["rms_norm_eps"])
    outer, xs = _forward(cfg, seed, ids, plens, None, weights)
    best, second, got, _ = (np.asarray(a) for a in _head_reads(
        outer, xs, jnp.asarray(at), jnp.asarray(served), eps, None))
    margin, gap = best - second, best - got
    cgap = np.zeros_like(gap)
    if control is not None:
        _, low = _forward(cfg, seed, ids, plens, control, weights)
        first = _head_reads(outer, low, jnp.asarray(at), jnp.asarray(served),
                            eps, control)[3]
        del low
        cgap = best - np.asarray(_head_reads(
            outer, xs, jnp.asarray(at), first, eps, None)[2])
    return {"served_logit_gap": float(np.max(gap[read])),
            "control_logit_gap": float(np.max(cgap[read])),
            "tokens_read": int(read.sum()),
            "distinct_tokens": len(set(served[read].tolist())),
            "top2_margin_min": float(np.min(margin[read])),
            "top2_margin_median": float(np.median(margin[read]))}
