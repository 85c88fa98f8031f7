"""Xing4.0 — a decoder with a four-stream hyper-connected residual, latent
attention with YaRN and sigmoid-routed experts beside a shared expert, for
generative serving.

The state between sub-layers is ``n = hc_mult`` residual streams, ``X (T, n,
d)``, not ``(T, d)``: every stream starts as the token's embedding and the
streams' sum goes to the final norm and the untied head. A sub-layer ``F``
(attention, or the feed-forward) reads ONE mix of the streams and writes to
all of them through three small maps of the token's own streams (the
manifold-constrained hyper-connection; everything of it in float32)::

    z      = RMS(X.reshape(T, n*d)) @ phi                 (no gain, hc_eps)
    H_pre  = sigmoid(a_pre * z[:, :n] + b_pre)
    H_post = 2 * sigmoid(a_post * z[:, n:2n] + b_post)
    H_res  = Sinkhorn(exp(clip(a_res * z[:, 2n:] + b_res, -30, 30)))   (n, n)
    u      = sum_i H_pre[i] X[:, i];      y = F(RMSNorm(u))
    X'[:, i] = sum_j H_res[i, j] X[:, j] + H_post[i] * y

``Sinkhorn`` divides each column by its sum and then each row by its sum,
``hc_sinkhorn_iters`` times, so ``H_res`` is doubly stochastic to within the
iteration's residual (:func:`hyper_maps` returns it: the step's statistics).

* **Layers.** The first ``first_k_dense_replace`` layers have a dense SwiGLU
  feed-forward, the others an expert layer: ``parallel.moe.moe_topk_share``
  scored by sigmoid with the chosen weights renormalised, plus the model's
  own shared expert, a SwiGLU every token takes. The router reads the
  normalised tokens in float32 (two of its scores within bfloat16's rounding
  of each other would otherwise pick by the rounding), the experts their
  cast to the weights' type.
* **Attention** is ``models/mla.py``'s (one latent row a token a layer in
  the paged cache, the absorbed decode), with YaRN's frequencies and scale.

The serving engine asks :class:`XingModel` for its programs and its cache
row like any other served model. No suffix prefill and no verify program
(the engine refuses ``prefix_pages``/``spec_k``); the multi-token-prediction
module of the published model is no part of the forward pass and is not
held (docs/SERVING.md).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.models import mla
from deeplearning4j_tpu.models.mla import rms as _rms, swiglu as _swiglu
from deeplearning4j_tpu.models.served import CacheRows, ServingPrograms
from deeplearning4j_tpu.parallel.moe import grouped_path, moe_topk_share

_YARN = mla.Yarn(factor=64.0, original_max_position_embeddings=4096,
                 beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                 mscale_all_dim=1.0)


@dataclasses.dataclass(frozen=True)
class XingConfig:
    """XingChen-AGI/Xing4.0-29B-A4B under the source's own keys (every
    expert and the whole vocabulary are held). ``tiny()`` for tests."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: int = 768
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[mla.Yarn] = _YARN
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    eos_token: int = 0

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):   # the source's own group
            keys = {k: v for k, v in self.rope_scaling.items()
                    if k in mla.Yarn._fields}
            object.__setattr__(self, "rope_scaling", mla.Yarn(**keys))

    @property
    def max_position(self) -> int:
        return self.max_position_embeddings

    @property
    def mla(self) -> mla.MlaDims:
        return mla.dims_of(self, yarn=self.rope_scaling)

    @property
    def hc_width(self) -> int:
        """The outputs of a sub-layer's ``phi``: H_pre, H_post, H_res."""
        return 2 * self.hc_mult + self.hc_mult * self.hc_mult

    @staticmethod
    def tiny(**kw) -> "XingConfig":
        """Test-sized, every mechanism kept: one dense and two expert
        layers, 16 experts top-4 and a shared one, four streams, YaRN."""
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 moe_intermediate_size=24, num_hidden_layers=3,
                 first_k_dense_replace=1, num_attention_heads=4,
                 kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8,
                 qk_nope_head_dim=8, v_head_dim=12, n_routed_experts=16,
                 max_position_embeddings=256)
        d.update(kw)
        return XingConfig(**d)


def init_xing_params(key, cfg: XingConfig, dtype=jnp.float32
                     ) -> Dict[str, Any]:
    """Parameter pytree: every matrix N(0, 1/fan_in), gains ones, the
    router's correction bias (a buffer) zeros; a sub-layer's hyper-connection
    ``phi`` N(0, 1/(n d)), its scalars ``a`` ones and its ``b`` nought but
    for ``b_res``'s diagonal of 2 (streams that mostly keep to themselves)."""
    d, f, w = (cfg.hidden_size, cfg.intermediate_size,
               cfg.moe_intermediate_size)
    h, rq, rkv = cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    n, e = cfg.hc_mult, cfg.n_routed_experts
    ks = iter(jax.random.split(key, 2 + cfg.num_hidden_layers * 24))

    def mat(*shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def ones(k):
        return jnp.ones((k,), dtype)

    def hc():
        b = jnp.concatenate([jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).reshape(-1)])
        return {"phi": mat(n * d, cfg.hc_width), "b": b.astype(dtype),
                "a": ones(3)}

    def ffn(width):
        return {"Wg": mat(d, width), "Wu": mat(d, width), "Wd": mat(width, d)}

    def layer(i):
        lp = {"attn": {"norm": ones(d), "W_qa": mat(d, rq),
                       "q_norm": ones(rq), "W_qb": mat(rq, h * (nope + rope)),
                       "W_kva": mat(d, rkv + rope), "kv_norm": ones(rkv),
                       "W_kvb": mat(rkv, h * (nope + dv)),
                       "W_o": mat(h * dv, d)},
              "hc": [hc(), hc()]}
        if i < cfg.first_k_dense_replace:
            lp["ffn"] = dict(ffn(f), norm=ones(d))
        else:
            lp["moe"] = {"norm": ones(d), "router": mat(d, e),
                         "bias": jnp.zeros((e,), dtype),
                         "Wg": mat(e, d, w), "Wu": mat(e, d, w),
                         "Wd": mat(e, w, d),
                         "shared": ffn(cfg.n_shared_experts * w)}
        return lp

    return {
        "embed": jax.random.normal(next(ks), (cfg.vocab_size, d),
                                   jnp.float32).astype(dtype),
        "final_norm": ones(d), "head": mat(d, cfg.vocab_size),
        "layers": [layer(i) for i in range(cfg.num_hidden_layers)]}


# -------------------------------------------------------- the residual path


def hyper_maps(hc, x, cfg: XingConfig, valid=None):
    """A sub-layer's three maps from the tokens' own streams. x: (T, n, d);
    ``hc``: ``phi (n*d, 2n + n*n)``, ``b (2n + n*n,)``, ``a (3,)``. Returns
    ``H_pre (n, T)``, ``H_post (n, T)``, ``H_res (n, n, T)`` (tokens last:
    the device's lanes hold tokens, and a row or column sum is an add of
    whole vectors) and the statistics over the ``valid`` tokens: the largest
    ``|row sum - 1|`` or ``|column sum - 1|`` of ``H_res`` after the
    iterations, and how many entries of ``R`` met the clamp."""
    t, n, d = x.shape
    lo, hi = cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max
    eps = cfg.hc_eps
    with jax.named_scope("hyper_connection"):
        x32 = x.astype(jnp.float32).reshape(t, n * d)
        xh = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
        z = jnp.dot(xh, hc["phi"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST).T
        a = hc["a"].astype(jnp.float32)
        b = hc["b"].astype(jnp.float32)[:, None]
        h_pre = jax.nn.sigmoid(a[0] * z[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * z[n:2 * n] + b[n:2 * n])
        raw = a[2] * z[2 * n:] + b[2 * n:]                     # (n*n, T)
        seen = jnp.ones((t,), bool) if valid is None else valid
        clamped = jnp.sum(((raw <= lo) | (raw >= hi)) & seen[None],
                          dtype=jnp.int32)
        m = jnp.exp(jnp.clip(raw, lo, hi)).reshape(n, n, t)    # [i, j, t]
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)  # columns
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)  # rows
        off = jnp.maximum(jnp.abs(jnp.sum(m, axis=1) - 1.0),
                          jnp.abs(jnp.sum(m, axis=0) - 1.0))   # (n, T)
        residual = jnp.max(jnp.where(seen[None], off, 0.0))
    return h_pre, h_post, m, (residual, clamped)


def hyper_sublayer(hc, x, fn, cfg: XingConfig, valid=None):
    """One hyper-connected sub-layer over the streams x (T, n, d), float32:
    ``fn(u (T, d)) -> (y (T, d), extra)`` is the sub-layer with its own
    gained norm. Returns ``(x', extra, (residual, clamped))``."""
    n = cfg.hc_mult
    h_pre, h_post, h_res, stats = hyper_maps(hc, x, cfg, valid)
    with jax.named_scope("hyper_connection"):
        u = sum(h_pre[i][:, None] * x[:, i] for i in range(n))
    y, extra = fn(u)
    with jax.named_scope("hyper_connection"):
        y = y.astype(jnp.float32)
        out = jnp.stack(
            [sum(h_res[i, j][:, None] * x[:, j] for j in range(n))
             + h_post[i][:, None] * y for i in range(n)], axis=1)
    return out, extra, stats


# ------------------------------------------------------------------ layers


def _experts(m, un, cfg: XingConfig, valid, act):
    """The routed experts' sum and the shared expert, over normalised
    tokens un (T, d) float32: the router reads them as they are, the experts
    in the weights' type ``act``. Returns (y float32, the router's
    statistics)."""
    y, stats = moe_topk_share(
        m, un, top_k=cfg.num_experts_per_tok, n_routed=cfg.n_routed_experts,
        n_zero=0, scale=cfg.routed_scaling_factor,
        held=(0, cfg.n_routed_experts), bias=m["bias"], valid=valid,
        score=cfg.scoring_func, renormalise=cfg.norm_topk_prob)
    with jax.named_scope("shared_expert"):
        shared = _swiglu(m["shared"], un.astype(act))
    return y + shared.astype(jnp.float32), stats


def _layer(lp, x, cfg: XingConfig, attend, valid, act):
    """One layer over the streams x (T, n, d) float32; ``attend(a, xn)`` is
    the path's attention over normalised tokens in the weights' type
    ``act``. Returns (x', the expert layer's statistics or None, the two
    sub-layers' hyper-connection statistics as a list)."""
    eps = cfg.rms_norm_eps
    a = lp["attn"]

    def attention(u):
        return attend(a, _rms(u, a["norm"], eps).astype(act)), None

    def feed_forward(u):
        if "moe" in lp:
            return _experts(lp["moe"], _rms(u, lp["moe"]["norm"], eps), cfg,
                            valid, act)
        return _swiglu(lp["ffn"], _rms(u, lp["ffn"]["norm"], eps).astype(act)
                       ), None

    x, _, hc_a = hyper_sublayer(lp["hc"][0], x, attention, cfg, valid)
    x, moe, hc_f = hyper_sublayer(lp["hc"][1], x, feed_forward, cfg, valid)
    return x, moe, [hc_a, hc_f]


def _streams(params, tokens, cfg: XingConfig):
    """Every stream starts as the token's embedding. (T,) -> (T, n, d)."""
    e = params["embed"][tokens].astype(jnp.float32)
    return jnp.broadcast_to(e[:, None, :], (e.shape[0], cfg.hc_mult,
                                            e.shape[1]))


def _logits(params, x, cfg: XingConfig):
    """The streams' sum through the final norm and the head, float32."""
    h = _rms(jnp.sum(x, axis=1), params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h.astype(params["head"].dtype), params["head"],
                   preferred_element_type=jnp.float32)


def _statistics(moe, hc):
    """What a program hands out beside its logits: the expert layers'
    integers ``(expert layers, experts + 2)`` and the hyper-connection's
    worst Sinkhorn residual and clamp count over the program's sub-layers."""
    return {"moe": jnp.stack(moe),
            "hc_residual": jnp.max(jnp.stack([r for r, _ in hc])),
            "hc_clamped": jnp.sum(jnp.stack([c for _, c in hc]))}


# ---------------------------------------------------------------- programs


def xing_prefill(params, ids, cfg: XingConfig, *, mask=None, last=None):
    """Causal full-prompt forward of ONE prompt. ids: (1, T) int32; mask:
    optional (1, T), 1 = real token (end padding); ``last``: optional scalar
    position: the logits of that position alone are computed, ``(1, V)``,
    else all ``(1, T, V)``. Returns ``(logits float32, rows (L, 1, 1, T, W),
    stats)``: the cache row of every layer's attention and
    :func:`_statistics` over the real tokens."""
    n, t = ids.shape
    if n != 1:
        raise ValueError("xing_prefill takes one prompt a call")
    pos = jnp.arange(t)
    valid = jnp.ones((t,), bool) if mask is None else mask[0].astype(bool)
    m4 = valid[None, None, None, :]
    x = _streams(params, ids[0], cfg)
    act = params["embed"].dtype
    rows, moe, hc = [], [], []

    def attend(a, xn):
        out, row = mla.prefill_attention(a, xn, pos, m4, cfg.mla)
        rows.append(row)
        return out

    for lp in params["layers"]:
        x, st, pair = _layer(lp, x, cfg, attend, valid, act)
        hc += pair
        if st is not None:
            moe.append(st)
    if last is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    logits = _logits(params, x, cfg)
    logits = logits if last is not None else logits[None]
    return logits, jnp.stack(rows)[:, None, None], _statistics(moe, hc)


def xing_decode_step(params, kv_pages, tokens, positions, page_table,
                     seq_lens_incl, write_page, write_offset,
                     cfg: XingConfig):
    """One decode token for every slot against the latent paged pool ``(L,
    1, P, page, W)``, updated in place (donate it). The arguments are
    ``gpt_decode_step``'s. Returns ``(kv_pages, logits (S, V) float32,
    stats)``; the statistics count the active slots' tokens only."""
    valid = seq_lens_incl > positions
    x = _streams(params, tokens, cfg)
    act = params["embed"].dtype
    moe, hc = [], []
    for li, lp in enumerate(params["layers"]):

        def attend(a, xn, li=li):
            nonlocal kv_pages
            kv_pages, out = mla.decode_attention(
                a, xn, positions, kv_pages, page_table, seq_lens_incl,
                write_page, write_offset, li, cfg.mla)
            return out

        x, st, pair = _layer(lp, x, cfg, attend, valid, act)
        hc += pair
        if st is not None:
            moe.append(st)
    return kv_pages, _logits(params, x, cfg), _statistics(moe, hc)


def note_xing_stats(cfg: XingConfig, stats, span=None, *,
                    decode_step: bool = False, tokens=None) -> None:
    """What the parts of a program's statistics mean: the expert layers'
    integers go to ``observe.note_moe`` (every expert is held: the first is
    0 and no pick is absent) with how the grouped products of a program over
    ``tokens`` token rows engaged, the hyper-connection's two numbers to
    ``observe.note_hyper_connection``."""
    observe.note_moe(
        stats["moe"], span, first_expert=0, decode_step=decode_step,
        grouped=tokens and grouped_path(
            stats["moe"], tokens, top_k=cfg.num_experts_per_tok,
            outputs=cfg.n_routed_experts))
    observe.note_hyper_connection(stats["hc_residual"], stats["hc_clamped"],
                                  span)


class XingModel:
    """Model handle: config + params, and what the serving engine asks a
    model for (``cache_rows``, ``serving_programs``)."""

    def __init__(self, cfg: XingConfig, seed: int = 0, dtype=jnp.float32,
                 params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.params = params if params is not None else init_xing_params(
            jax.random.key(seed), cfg, dtype)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.params))

    def cache_rows(self) -> CacheRows:
        return xing_cache_rows(self.cfg)

    def serving_programs(self) -> ServingPrograms:
        return xing_programs(self.cfg)


def xing_cache_rows(cfg: XingConfig) -> CacheRows:
    """One latent row a token a layer, one side."""
    return CacheRows(layers=cfg.num_hidden_layers, sides=1,
                     width=mla.cache_row_width(cfg.mla))


def xing_programs(cfg: XingConfig) -> ServingPrograms:
    """The jittable programs, bound to ``cfg`` (needs no weights)."""

    def prefill(params, ids, prompt_len):
        mask = (jnp.arange(ids.shape[1]) < prompt_len)[None, :]
        logits, rows, stats = xing_prefill(
            params, ids, cfg, mask=mask.astype(jnp.int32),
            last=prompt_len - 1)
        return logits, rows[:, :, 0], stats

    def decode_step(params, kv_pages, tokens, positions, page_table,
                    seq_lens_incl, write_page, write_offset):
        return xing_decode_step(
            params, kv_pages, tokens, positions, page_table, seq_lens_incl,
            write_page, write_offset, cfg)

    return ServingPrograms(prefill=prefill, decode_step=decode_step,
                           note_stats=functools.partial(note_xing_stats, cfg))
