"""Plain reference for the ``longcat`` family: LongCat-Flash's language
model, full-sequence forward in straightforward jax.numpy, float32 at
``highest`` matmul precision. No cache, no paging, no batching, no kernels,
nothing of the program.

One layer has two latent-attention (MLA) sub-layers ``a`` and ``b``, two
dense SwiGLU feed-forwards and ONE expert layer that reads the first
sub-layer's normalised input and is added at the END of the layer (the
shortcut)::

    h = x + MLA_a(RMS(x));  u = RMS(h);  m = MoE(u);  h = h + SwiGLU_a(u)
    h = h + MLA_b(RMS(h));  x' = h + SwiGLU_b(RMS(h)) + m

``MoE`` routes over ``n_routed + zero_expert_num`` outputs (softmax in
float32, the top ``moe_topk`` of ``s + bias``, weights ``routed_scaling_factor
* s`` not renormalised); a routed expert is a SwiGLU of width
``expert_ffn_hidden_size``, a zero expert returns its input. THE SHARE: this
chip holds ``held = (first, count)`` of the routed experts and a slice of the
vocabulary. It routes over all outputs, adds the terms of its held experts
and of the zero experts, and leaves out the absent experts' terms; that
partial sum goes on to the next layer (the configuration's ``deployment``).
With ``held = (0, n_routed)`` this is the uncut model.

Every departure from the published description is the configuration's
``assumed``. The weights are the values the program holds (drawn from the
seed in the served type, a layer at a time) widened to float32 a layer at a
time: all four layers in float32 do not fit the chip beside each other, so
no more than one layer's weights exist at any moment.

Stand-ins (``control=``): ``"float8"`` and ``"int8"`` round every matmul
operand to that type's levels (per-tensor absmax scale), the nearest
precisions below the bfloat16 the configuration states; ``"no_zero_experts"``
and ``"no_kv_scale"`` are faults planted in the two new parts (the identity
experts' term left out; ``mla_scale_kv_lora`` left out).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.seeded import Leaf, _make_leaves, seed_key

CONTROLS = ("float8", "int8")
FAULTS = ("no_zero_experts", "no_kv_scale")


# ------------------------------------------------------------------- sizes


def router_width(cfg: Dict[str, Any]) -> int:
    """The router's outputs: every published routed expert and every zero
    expert, whatever is held here."""
    return routed_total(cfg) + int(cfg["zero_expert_num"])


def routed_total(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def held(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(first, count) of the routed experts held here."""
    return int(cfg.get("held_experts_first", 0)), int(cfg["n_routed_experts"])


class Static(NamedTuple):
    """What the jitted functions need of the configuration, hashable."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    eps: float
    theta: float
    scale_q: bool
    scale_kv: bool
    factor: float
    top_k: int
    n_routed: int
    n_zero: int
    held: Tuple[int, int]
    hidden: int


def _static(cfg: Dict[str, Any]) -> Static:
    return Static(
        int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]),
        int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        bool(cfg["mla_scale_q_lora"]), bool(cfg["mla_scale_kv_lora"]),
        float(cfg["routed_scaling_factor"]), int(cfg["moe_topk"]),
        routed_total(cfg), int(cfg["zero_expert_num"]), held(cfg),
        int(cfg["hidden_size"]))


# ----------------------------------------------------------------- weights


def layer_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's parameter tree (the program's names). ``cfg["init"]``
    gives each matrix's gain: sigma = gain / sqrt(fan_in), so that a matrix
    of gain 1 keeps the size of what it is given; the configuration file
    says why the gains are what they are."""
    d, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    w = cfg["expert_ffn_hidden_size"]
    h = cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    gains = cfg.get("init", {})
    n_held = held(cfg)[1]

    def mat(name, *shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return Leaf("normal", shape, gains.get(name, 1.0) / math.sqrt(fan_in))

    def attn():
        return {"norm": Leaf("ones", (d,)),
                "W_qa": mat("W_qa", d, rq), "q_norm": Leaf("ones", (rq,)),
                "W_qb": mat("W_qb", rq, h * (nope + rope)),
                "W_kva": mat("W_kva", d, rkv + rope),
                "kv_norm": Leaf("ones", (rkv,)),
                "W_kvb": mat("W_kvb", rkv, h * (nope + dv)),
                "W_o": mat("W_o", h * dv, d)}

    def ffn():
        return {"norm": Leaf("ones", (d,)), "Wg": mat("Wg", d, f),
                "Wu": mat("Wu", d, f), "Wd": mat("Wd", f, d)}

    return {"attn": [attn(), attn()], "ffn": [ffn(), ffn()],
            "moe": {"router": mat("router", d, router_width(cfg)),
                    "bias": Leaf("zeros", (router_width(cfg),)),
                    "Wg": mat("expert_Wg", n_held, d, w),
                    "Wu": mat("expert_Wu", n_held, d, w),
                    "Wd": mat("expert_Wd", n_held, w, d)}}


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
    group ``i >= 0`` is layer ``i``. ``seeded.make_weights`` draws a whole
    model at once through float32; at this model's size its temporaries do
    not fit beside its results, so the program and the reference both draw a
    group at a time, with this function, and so hold the same values."""
    spec = outer_spec(cfg) if group < 0 else layer_spec(cfg)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, Leaf))
    kinds = tuple((lf.kind, lf.shape, lf.sigma, lf.center) for lf in leaves)
    key = jax.random.fold_in(seed_key(seed), group + 1)
    return treedef.unflatten(_make_leaves(key, kinds, jnp.dtype(dtype).name))


def make_weights(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole tree (what the program holds)."""
    return {**make_group(cfg, seed, -1, dtype),
            "layers": [make_group(cfg, seed, i, dtype)
                       for i in range(cfg["num_layers"])]}


# ----------------------------------------------------------------- rounding


def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _float8(x):
    """Round to float8 (e4m3) levels of a per-tensor absmax scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _rounding(control: Optional[str]):
    return {"int8": _int8, "float8": _float8}.get(control, lambda x: x)


# --------------------------------------------------------------- the layers


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rope(x, pos, theta):
    """Rotate the pairs (x[2i], x[2i+1]) of the last axis by the angle
    ``pos * theta**(-2i/n)`` (pairs interleaved). x: (T, ..., n), pos: (T,)."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (T, n/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (n // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.reshape(x.shape[:-1] + (n // 2, 2))
    even, odd = pair[..., 0], pair[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def swiglu(x, wg, wu, wd, q):
    return q(jax.nn.silu(q(x) @ q(wg)) * (q(x) @ q(wu))) @ q(wd)


def mla(w, x, st, q, fault, block: int = 512):
    """Latent attention over the whole sequence, causal. x: (T, d), already
    normalised. Scores of a block of queries at a time."""
    h, rq, rkv, nope, rp, dv = st[:6]
    eps, theta, d = st.eps, st.theta, st.hidden
    t = x.shape[0]
    pos = jnp.arange(t)
    s_q = math.sqrt(d / rq) if st.scale_q else 1.0
    s_kv = math.sqrt(d / rkv) if st.scale_kv and fault != "no_kv_scale" else 1.0
    c_q = rms_norm(q(x) @ q(w["W_qa"]), w["q_norm"], eps)
    qh = (q(c_q) @ q(w["W_qb"])).reshape(t, h, nope + rp) * s_q
    q_nope, q_rope = qh[..., :nope], rope(qh[..., nope:], pos, theta)
    ckr = q(x) @ q(w["W_kva"])
    c = rms_norm(ckr[:, :rkv], w["kv_norm"], eps) * s_kv
    k_r = rope(ckr[:, rkv:], pos, theta)                        # (T, rp)
    kv = (q(c) @ q(w["W_kvb"])).reshape(t, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    outs = []
    for s0 in range(0, t, block):
        qn, qr = q_nope[s0:s0 + block], q_rope[s0:s0 + block]
        s = (jnp.einsum("qhn,khn->hqk", q(qn), q(k_nope))
             + jnp.einsum("qhr,kr->hqk", q(qr), q(k_r))) / math.sqrt(nope + rp)
        ok = pos[None, :] <= pos[s0:s0 + block, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("hqk,khv->qhv", q(p), q(v)))
    o = jnp.concatenate(outs, axis=0).reshape(t, h * dv)
    return q(o) @ q(w["W_o"])


def route(w, u, st):
    """(T, d) -> the chosen outputs (T, k) and their weights (T, k)."""
    s = jax.nn.softmax(u @ w["router"].astype(jnp.float32), axis=-1)
    _, chosen = jax.lax.top_k(s + w["bias"].astype(jnp.float32), st.top_k)
    return chosen, st.factor * jnp.take_along_axis(s, chosen, axis=-1)


def moe(w, u, st, q, fault):
    """The share's partial sum: the held experts' terms, every held expert
    over every token with the router's weight (nought where not chosen), and
    the zero experts' term."""
    n_routed, (first, count) = st.n_routed, st.held
    chosen, weight = route(w, u, st)          # the router is never rounded
    y = jnp.zeros_like(u)
    for e in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(u, w["Wg"][e], w["Wu"][e], w["Wd"][e], q)
    if fault != "no_zero_experts":
        w_zero = jnp.sum(jnp.where(chosen >= n_routed, weight, 0.0), axis=-1)
        y = y + w_zero[:, None] * u
    return y


def layer(w, x, st, q=lambda x: x, fault=None):
    eps = st.eps
    a, b = w["attn"]
    fa, fb = w["ffn"]
    h = x + mla(a, rms_norm(x, a["norm"], eps), st, q, fault)
    u = rms_norm(h, fa["norm"], eps)
    m = moe(w["moe"], u, st, q, fault)
    h = h + swiglu(u, fa["Wg"], fa["Wu"], fa["Wd"], q)
    h = h + mla(b, rms_norm(h, b["norm"], eps), st, q, fault)
    return h + swiglu(rms_norm(h, fb["norm"], eps), fb["Wg"], fb["Wu"],
                      fb["Wd"], q) + m


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_over(w, xs, st, control):
    """One layer over each sequence in turn (xs: (B, T, d) float32), its
    weights widened to float32 here, for this call alone."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    fault = control if control in FAULTS else None
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda x: layer(w, x, st, _rounding(control), fault), xs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_over(outer, xs, at, eps, control):
    q = _rounding(control)
    outer = jax.tree.map(lambda a: a.astype(jnp.float32), outer)
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(xs, at[:, :, None], axis=1)     # (B, K, d)
        return q(rms_norm(x, outer["final_norm"], eps)) @ q(outer["head"])


def logits_at(cfg: Dict[str, Any], seed: int, ids, at, *, control=None,
              weights=None):
    """ids (B, T) int32, positions ``at`` (B, K) -> logits (B, K, V) of the
    causal forward over each whole row of ``ids``. Padding after the last
    position read changes nothing before it. ``weights``: a whole tree to
    use in place of the seed's (the CPU tests)."""
    dtype = jnp.dtype(cfg["param_dtype"])
    st = _static(cfg)

    def group(i):
        if weights is None:
            return make_group(cfg, seed, i, dtype)
        return (weights["layers"][i] if i >= 0 else
                {k: weights[k] for k in ("embed", "final_norm", "head")})

    outer = group(-1)
    xs = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        xs = _layer_over(group(i), xs, st, control)
    return _head_over(outer, xs, jnp.asarray(at), st.eps, control)


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
    for i, req in enumerate(sample):
        prompt = np.asarray(req["prompt"], np.int32)
        toks = np.asarray(req["tokens"], np.int32)[:max_new]
        full = np.concatenate([prompt, toks])[:max_total]
        ids[i, :len(full)] = full
        n = min(len(toks), max_total - len(prompt) + 1)
        at[i] = len(prompt) - 1
        at[i, :n] = len(prompt) - 1 + np.arange(n)
        served[i, :n] = toks[:n]
        read[i, :n] = True
    ref = np.asarray(logits_at(cfg, seed, ids, at, weights=weights))
    order = np.sort(ref, axis=-1)
    best, margin = order[..., -1], order[..., -1] - order[..., -2]
    gap = best - np.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    cgap = np.zeros_like(gap)
    if control is not None:
        low = np.asarray(logits_at(cfg, seed, ids, at, control=control,
                                   weights=weights))
        first = np.argmax(low, axis=-1)
        cgap = best - np.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
    return {"served_logit_gap": float(np.max(gap[read])),
            "control_logit_gap": float(np.max(cgap[read])),
            "tokens_read": int(read.sum()),
            "distinct_tokens": len(set(served[read].tolist())),
            "top2_margin_min": float(np.min(margin[read])),
            "top2_margin_median": float(np.median(margin[read]))}
