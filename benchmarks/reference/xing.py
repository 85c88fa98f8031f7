"""Plain reference for the ``xing`` family: Xing4.0's language model,
full-sequence forward in straightforward jax.numpy, float32 at ``highest``
matmul precision. No cache, no paging, no batching, no kernels, nothing of
the program.

The state between sub-layers is ``n = hc_mult`` residual streams ``X (T, n,
d)``: every stream starts as the token's embedding, and after the last layer
their sum goes through the final RMSNorm and the untied head. A layer is two
hyper-connected sub-layers, latent attention (MLA, YaRN) and a feed-forward
(dense SwiGLU in the first ``first_k_dense_replace`` layers, else the expert
layer), each with its own ``phi (n*d, 2n + n*n)``, ``b`` and scalars
``a = (a_pre, a_post, a_res)``::

    z = RMS(X.reshape(T, n*d)) @ phi                       (no gain, hc_eps)
    H_pre = sigmoid(a_pre z[:, :n] + b_pre);  H_post = 2 sigmoid(a_post z[:, n:2n] + b_post)
    R = clip(a_res z[:, 2n:] + b_res, clamp_min, clamp_max).reshape(n, n)
    H_res = exp(R), then hc_sinkhorn_iters times: each column over its sum,
            then each row over its sum (+ hc_eps)
    u = sum_i H_pre[i] X[:, i];   y = F(RMSNorm(u))
    X'[:, i] = sum_j H_res[i, j] X[:, j] + H_post[i] y

The expert layer: ``s = sigmoid(u @ W_r)`` over all routed experts, the
chosen are the top ``num_experts_per_tok`` of ``s + bias``, their weights
``routed_scaling_factor * s / (sum of the chosen s + 1e-20)``, and one shared
SwiGLU that every token takes. MLA is the latent attention of
``reference/longcat.py`` without its two scales and with YaRN: the rotation's
frequencies blended between ``theta^(-2i/n)`` and that over ``factor`` by the
linear ramp between the correction dimensions of ``beta_fast`` and
``beta_slow``, the softmax scaled by ``(nope + rope)^-0.5 * mscale(factor,
mscale_all_dim)^2``.

Every departure from the published description is the configuration's
``assumed``. The weights are the values the program holds (drawn from the
seed in the served type, a layer at a time) widened to float32 a layer at a
time.

Stand-ins (``control=``): ``"float8"`` and ``"int8"`` round every matmul
operand of attention, feed-forwards, experts and head to that type's levels
(the router's and the hyper-connection's products stay float32, as a
deployment in those types would keep them); the faults are planted one in
each new part: ``"no_sinkhorn"`` (``H_res = exp(R)`` with each row over its
sum, once), ``"static_hc"`` (the ``a * z`` terms left out: constant maps),
``"no_shared_expert"``, ``"no_renorm"`` (weights ``factor * s``) and
``"no_yarn"`` (plain frequencies, plain scale).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# what every family's reference rounds and normalises alike
from reference.longcat import _rounding, rms_norm, swiglu
from reference.seeded import Leaf, _make_leaves, seed_key

CONTROLS = ("float8", "int8")
FAULTS = ("no_sinkhorn", "static_hc", "no_shared_expert", "no_renorm",
          "no_yarn")


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
    yarn: tuple          # factor, original positions, beta_fast, beta_slow,
    #                      mscale, mscale_all_dim
    factor: float
    top_k: int
    renorm: bool
    streams: int
    iters: int
    hc_eps: float
    clamp: tuple


def _static(cfg: Dict[str, Any]) -> Static:
    rs = cfg["rope_scaling"]
    return Static(
        int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]),
        int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
         float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
         float(rs["mscale_all_dim"])),
        float(cfg["routed_scaling_factor"]), int(cfg["num_experts_per_tok"]),
        bool(cfg["norm_topk_prob"]), int(cfg["hc_mult"]),
        int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]),
        (float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])))


# ----------------------------------------------------------------- weights


def is_dense(cfg: Dict[str, Any], layer: int) -> bool:
    return layer < int(cfg["first_k_dense_replace"])


def layer_spec(cfg: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """One layer's parameter tree (the program's names). ``cfg["init"]``
    gives each matrix's gain: sigma = gain / sqrt(fan_in); the configuration
    file says why the gains are what they are. ``hc[k]["b"]`` is drawn as
    noise here and given its constant part by :func:`make_group`."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    w, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    h, n = cfg["num_attention_heads"], cfg["hc_mult"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    gains = cfg.get("init", {})

    def mat(name, *shape):
        return Leaf("normal", shape,
                    gains.get(name, 1.0) / math.sqrt(shape[-2]))

    def hc():
        return {"phi": mat("hc_phi", n * d, 2 * n + n * n),
                "b": Leaf("normal", (2 * n + n * n,),
                          gains.get("hc_b_sigma", 0.0)),
                "a": Leaf("ones", (3,))}

    spec = {"attn": {"norm": Leaf("ones", (d,)),
                     "W_qa": mat("W_qa", d, rq), "q_norm": Leaf("ones", (rq,)),
                     "W_qb": mat("W_qb", rq, h * (nope + rope)),
                     "W_kva": mat("W_kva", d, rkv + rope),
                     "kv_norm": Leaf("ones", (rkv,)),
                     "W_kvb": mat("W_kvb", rkv, h * (nope + dv)),
                     "W_o": mat("W_o", h * dv, d)},
            "hc": [hc(), hc()]}
    if is_dense(cfg, layer):
        spec["ffn"] = {"norm": Leaf("ones", (d,)), "Wg": mat("Wg", d, f),
                       "Wu": mat("Wu", d, f), "Wd": mat("Wd", f, d)}
    else:
        ws = cfg["n_shared_experts"] * w
        spec["moe"] = {"norm": Leaf("ones", (d,)),
                       "router": mat("router", d, e),
                       "bias": Leaf("zeros", (e,)),
                       "Wg": mat("expert_Wg", e, d, w),
                       "Wu": mat("expert_Wu", e, d, w),
                       "Wd": mat("expert_Wd", e, w, d),
                       "shared": {"Wg": mat("shared_Wg", d, ws),
                                  "Wu": mat("shared_Wu", d, ws),
                                  "Wd": mat("shared_Wd", ws, d)}}
    return spec


def outer_spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    gains = cfg.get("init", {})
    return {"embed": Leaf("normal", (v, d), gains.get("embed_sigma", 1.0)),
            "final_norm": Leaf("ones", (d,)),
            "head": Leaf("normal", (d, v),
                         gains.get("head", 1.0) / math.sqrt(d))}


def hc_constants(cfg: Dict[str, Any]):
    """The constant part of a sub-layer's ``b`` (``hc_b_pre`` and
    ``hc_b_post`` on every stream, ``hc_b_res_diag`` on ``b_res``'s diagonal)
    and its scalars ``a``, from the configuration's ``init``."""
    gains, n = cfg.get("init", {}), cfg["hc_mult"]
    b = np.concatenate([
        np.full(n, gains.get("hc_b_pre", 0.0)),
        np.full(n, gains.get("hc_b_post", 0.0)),
        (gains.get("hc_b_res_diag", 0.0) * np.eye(n)).reshape(-1)])
    a = np.array([gains.get("hc_a_pre", 1.0), gains.get("hc_a_post", 1.0),
                  gains.get("hc_a_res", 1.0)])
    return b.astype(np.float32), a.astype(np.float32)


def make_group(cfg: Dict[str, Any], seed: int, group: int, dtype) -> Any:
    """The weights of one group, drawn from the seed in ONE jitted call:
    group -1 is what lies outside the layers (embedding, final norm, head),
    group ``i >= 0`` is layer ``i``. The program and the reference both draw
    a group at a time, with this function, and so hold the same values."""
    spec = outer_spec(cfg) if group < 0 else layer_spec(cfg, group)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, Leaf))
    kinds = tuple((lf.kind, lf.shape, lf.sigma, lf.center) for lf in leaves)
    key = jax.random.fold_in(seed_key(seed), group + 1)
    tree = treedef.unflatten(_make_leaves(key, kinds, jnp.dtype(dtype).name))
    if group >= 0:
        # on the host, in the served type: two dozen numbers, no program
        b0, a0 = hc_constants(cfg)
        for hc in tree["hc"]:
            noise = np.asarray(hc["b"]).astype(np.float32)
            hc["b"] = jnp.asarray((noise + b0).astype(jnp.dtype(dtype)))
            hc["a"] = jnp.asarray(a0.astype(jnp.dtype(dtype)))
    return tree


def make_weights(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole tree (what the program holds)."""
    return {**make_group(cfg, seed, -1, dtype),
            "layers": [make_group(cfg, seed, i, dtype)
                       for i in range(cfg["num_hidden_layers"])]}


# --------------------------------------------------------------- the layers


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(n: int, theta: float, yarn: Optional[tuple]):
    """The rotation's ``n // 2`` frequencies. Plain: ``theta^(-2i/n)``. YaRN:
    a pair that turns more than ``beta_fast`` times over the original
    positions keeps its frequency, one that turns fewer than ``beta_slow``
    times has it divided by ``factor``, with a linear ramp between the two
    correction dimensions (the floor of the first, the ceiling of the
    second)."""
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    if yarn is None:
        return inv
    factor, original, fast, slow = yarn[:4]

    def correction_dim(turns):
        return n * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), n - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(n // 2) - low) / (high - low), 0.0, 1.0)
    ramp = jnp.asarray(ramp, jnp.float32)
    return inv / factor * ramp + inv * (1.0 - ramp)


def softmax_scale(st: Static, fault=None) -> float:
    scale = 1.0 / math.sqrt(st.nope + st.rope)
    if fault != "no_yarn":
        scale *= yarn_mscale(st.yarn[0], st.yarn[5]) ** 2
    return scale


def rope(x, pos, theta, yarn):
    """Rotate the pairs (x[2i], x[2i+1]) of the last axis by ``pos *
    inv_freq[i]`` (pairs interleaved); YaRN's cosines and sines times
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
    x: (T, ..., n), pos: (T,)."""
    n = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * inv_freq(n, theta, yarn)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (n // 2,))
    size = 1.0 if yarn is None else (yarn_mscale(yarn[0], yarn[4])
                                     / yarn_mscale(yarn[0], yarn[5]))
    cos, sin = jnp.cos(ang) * size, jnp.sin(ang) * size
    pair = x.reshape(x.shape[:-1] + (n // 2, 2))
    even, odd = pair[..., 0], pair[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def mla(w, x, st: Static, q, fault, block: int = 512):
    """Latent attention over the whole sequence, causal. x: (T, d), already
    normalised. Scores of a block of queries at a time."""
    h, rq, rkv, nope, rp, dv = st[:6]
    eps, theta = st.eps, st.theta
    yarn = None if fault == "no_yarn" else st.yarn
    t = x.shape[0]
    pos = jnp.arange(t)
    c_q = rms_norm(q(x) @ q(w["W_qa"]), w["q_norm"], eps)
    qh = (q(c_q) @ q(w["W_qb"])).reshape(t, h, nope + rp)
    q_nope, q_rope = qh[..., :nope], rope(qh[..., nope:], pos, theta, yarn)
    ckr = q(x) @ q(w["W_kva"])
    c = rms_norm(ckr[:, :rkv], w["kv_norm"], eps)
    k_r = rope(ckr[:, rkv:], pos, theta, yarn)                  # (T, rp)
    kv = (q(c) @ q(w["W_kvb"])).reshape(t, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(st, fault)
    outs = []
    for s0 in range(0, t, block):
        qn, qr = q_nope[s0:s0 + block], q_rope[s0:s0 + block]
        s = (jnp.einsum("qhn,khn->hqk", q(qn), q(k_nope))
             + jnp.einsum("qhr,kr->hqk", q(qr), q(k_r))) * scale
        ok = pos[None, :] <= pos[s0:s0 + block, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("hqk,khv->qhv", q(p), q(v)))
    o = jnp.concatenate(outs, axis=0).reshape(t, h * dv)
    return q(o) @ q(w["W_o"])


def route(w, u, st: Static, fault=None):
    """(T, d) -> the chosen experts (T, k) and their weights (T, k)."""
    s = jax.nn.sigmoid(u @ w["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + w["bias"].astype(jnp.float32), st.top_k)
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    if st.renorm and fault != "no_renorm":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, st.factor * weight


def moe(w, u, st: Static, q, fault, experts=None):
    """Every routed expert over every token with the router's weight (nought
    where not chosen), one after another, plus the shared expert.
    ``experts = (first, count)``: those routed experts' terms alone and no
    shared expert (one share of the layer, for the tests)."""
    chosen, weight = route(w, u, st, fault)   # the router is never rounded
    first, count = experts or (0, w["Wg"].shape[0])

    def one(y, ew):
        e, wg, wu, wd = ew
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(u, wg, wu, wd, q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        first + jnp.arange(count), w["Wg"][first:first + count],
        w["Wu"][first:first + count], w["Wd"][first:first + count]))
    if experts is None and fault != "no_shared_expert":
        sh = w["shared"]
        y = y + swiglu(u, sh["Wg"], sh["Wu"], sh["Wd"], q)
    return y


def sinkhorn(r, st: Static, fault=None):
    """(T, n, n) logits -> H_res: ``exp`` of the clamped logits, then
    ``iters`` times each column over its sum and each row over its sum."""
    m = jnp.exp(jnp.clip(r, st.clamp[0], st.clamp[1]))
    if fault == "no_sinkhorn":
        return m / (jnp.sum(m, axis=-1, keepdims=True) + st.hc_eps)
    for _ in range(st.iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + st.hc_eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + st.hc_eps)
    return m


def hyper_maps(hc, x, st: Static, fault=None):
    """x (T, n, d) -> H_pre (T, n), H_post (T, n), H_res (T, n, n)."""
    t, n, d = x.shape
    flat = x.reshape(t, n * d)
    xh = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                              + st.hc_eps)
    z = xh @ hc["phi"]
    if fault == "static_hc":
        z = jnp.zeros_like(z)
    a, b = hc["a"], hc["b"]
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    r = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    return h_pre, h_post, sinkhorn(r, st, fault)


def sublayer(hc, x, fn, st: Static, fault=None):
    """One hyper-connected sub-layer over the streams x (T, n, d)."""
    h_pre, h_post, h_res = hyper_maps(hc, x, st, fault)
    u = jnp.einsum("ti,tid->td", h_pre, x)
    y = fn(u)
    return jnp.einsum("tij,tjd->tid", h_res, x) + h_post[:, :, None] * y[:, None, :]


def layer(w, x, st: Static, q=lambda x: x, fault=None):
    eps = st.eps
    a = w["attn"]
    x = sublayer(w["hc"][0], x,
                 lambda u: mla(a, rms_norm(u, a["norm"], eps), st, q, fault),
                 st, fault)
    if "moe" in w:
        m = w["moe"]
        return sublayer(
            w["hc"][1], x,
            lambda u: moe(m, rms_norm(u, m["norm"], eps), st, q, fault),
            st, fault)
    f = w["ffn"]
    return sublayer(
        w["hc"][1], x,
        lambda u: swiglu(rms_norm(u, f["norm"], eps), f["Wg"], f["Wu"],
                         f["Wd"], q), st, fault)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_over(w, xs, st, control):
    """One layer over each sequence in turn (xs: (B, T, n, d) float32), its
    weights widened to float32 here, for this call alone."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    fault = control if control in FAULTS else None
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda x: layer(w, x, st, _rounding(control), fault), xs)


def _head_logits(outer, xs, at, eps, control):
    """float32 logits (K, V) of ONE sequence's streams xs (T, n, d) at the
    positions ``at`` (K,); ``outer``: final norm and head, float32."""
    q = _rounding(control)
    x = jnp.sum(xs, axis=1)[at]
    return q(rms_norm(x, outer["final_norm"], eps)) @ q(outer["head"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_over(outer, xs, at, eps, control):
    outer = jax.tree.map(lambda a: a.astype(jnp.float32), outer)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda xa: _head_logits(outer, xa[0], xa[1], eps, control),
            (xs, at))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_reads(outer, xs, at, tokens, eps, control):
    """What the comparison needs of the logits, a sequence at a time (the
    logits of one are (K, V) float32, half a gigabyte at the cell's size):
    the best logit and the second best (B, K), the logit of ``tokens`` (B,
    K) and the token put first (B, K)."""
    outer = jax.tree.map(lambda a: a.astype(jnp.float32), outer)

    def one(xat):
        x, a, tok = xat
        logits = _head_logits(outer, x, a, eps, control)
        first = jnp.argmax(logits, axis=-1)
        best = jnp.max(logits, axis=-1)
        others = jnp.where(jnp.arange(logits.shape[-1])[None, :]
                           == first[:, None], -jnp.inf, logits)
        got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
        return best, jnp.max(others, axis=-1), got, first

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (xs, at, tokens))


def _forward(cfg: Dict[str, Any], seed: int, ids, control, weights):
    """The streams after the last layer (B, T, n, d) float32, and what lies
    outside the layers (final norm and head, in the served type)."""
    dtype = jnp.dtype(cfg["param_dtype"])
    st = _static(cfg)

    def group(i):
        if weights is None:
            return make_group(cfg, seed, i, dtype)
        return (weights["layers"][i] if i >= 0 else
                {k: weights[k] for k in ("embed", "final_norm", "head")})

    outer = group(-1)
    e = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)    # (B, T, d)
    xs = jnp.broadcast_to(e[:, :, None, :],
                          e.shape[:2] + (st.streams, e.shape[2]))
    for i in range(cfg["num_hidden_layers"]):
        xs = _layer_over(group(i), xs, st, control)
    return {k: outer[k] for k in ("final_norm", "head")}, xs


def logits_at(cfg: Dict[str, Any], seed: int, ids, at, *, control=None,
              weights=None):
    """ids (B, T) int32, positions ``at`` (B, K) -> logits (B, K, V) of the
    causal forward over each whole row of ``ids``. Padding after the last
    position read changes nothing before it. ``weights``: a whole tree to
    use in place of the seed's (the CPU tests)."""
    outer, xs = _forward(cfg, seed, ids, control, weights)
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
    eps = float(cfg["rms_norm_eps"])
    outer, xs = _forward(cfg, seed, ids, None, weights)
    best, second, got, _ = (np.asarray(a) for a in _head_reads(
        outer, xs, jnp.asarray(at), jnp.asarray(served), eps, None))
    margin, gap = best - second, best - got
    cgap = np.zeros_like(gap)
    if control is not None:
        _, low = _forward(cfg, seed, ids, control, weights)
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
