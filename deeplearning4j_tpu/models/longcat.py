"""LongCat-Flash — the language model, for generative serving.

A decoder whose layer (the published "double layer") holds two latent
attention (MLA) sub-layers, two dense SwiGLU feed-forwards and one expert
layer that reads the first sub-layer's normalised input and is added at the
END of the layer, beside the second feed-forward (shortcut-connected
experts)::

    h = x + MLA_a(RMS(x));  u = RMS(h);  m = MoE(u);  h = h + SwiGLU_a(u)
    h = h + MLA_b(RMS(h));  x' = h + SwiGLU_b(RMS(h)) + m

* **MLA**: queries through a low-rank ``q_lora_rank`` bottleneck, keys and
  values through ONE shared latent of ``kv_lora_rank`` values a token plus a
  rotary key of ``qk_rope_head_dim`` shared by all heads. What a token leaves
  in the cache is one row an attention sub-layer: ``[RMS(c) * s_kv | rotated
  k_r]`` (:func:`cache_row_width` pads it to whole 128-lane tiles). The
  pieces are ``models/mla.py``'s, which every latent-attention model calls:
  :func:`longcat_prefill` materialises K and V from the latent and runs the
  registry's ``dot_product_attention``; :func:`longcat_decode_step` absorbs
  the up-projections into the query and the output and runs the registry's
  ``latent_decode_attention`` against the latent pool where it lies.
* **MoE** (``parallel.moe.moe_topk_share``): a softmax router over
  ``n_routed_experts + zero_expert_num`` outputs, top ``moe_topk``; a zero
  expert returns its input. The model is told which routed experts it HOLDS
  (``held_experts = (first, count)``, one expert-parallel rank's share): it
  routes over all, computes its own experts' and the zero experts' terms and
  leaves out the absent ones. Its vocabulary is the slice it holds.

The serving engine asks :class:`LongcatModel` for its programs and its cache
row (``serving_programs`` / ``cache_rows``) like any other served model. No
suffix prefill and no verify program yet: the engine refuses
``prefix_pages``/``spec_k`` for this model (docs/SERVING.md).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.models import mla
from deeplearning4j_tpu.models.mla import rms as _rms, swiglu as _swiglu
from deeplearning4j_tpu.models.served import CacheRows, ServingPrograms
from deeplearning4j_tpu.parallel.moe import grouped_path, moe_topk_share


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    """meituan-longcat/LongCat-Flash-Omni's language model, under the
    source's own keys; ``held_experts`` and ``vocab_size`` are what THIS
    rank holds (defaults: everything). ``tiny()`` for tests."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    held_experts: Optional[Tuple[int, int]] = None
    eos_token: int = 0

    @property
    def max_position(self) -> int:
        return self.max_position_embeddings

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    @property
    def mla(self) -> mla.MlaDims:
        return mla.dims_of(self, scale_q=self.mla_scale_q_lora,
                           scale_kv=self.mla_scale_kv_lora)

    @staticmethod
    def tiny(**kw) -> "LongcatConfig":
        """Test-sized, every mechanism kept: two MLA sub-layers a layer,
        8 routed + 4 zero experts, top-3."""
        d = dict(vocab_size=96, hidden_size=32, ffn_hidden_size=48,
                 expert_ffn_hidden_size=24, num_layers=2,
                 num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
                 qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=12,
                 n_routed_experts=8, zero_expert_num=4, moe_topk=3,
                 max_position_embeddings=256)
        d.update(kw)
        return LongcatConfig(**d)


def cache_row_width(cfg: LongcatConfig) -> int:
    """The cache row of this model's attention (``mla.cache_row_width``)."""
    return mla.cache_row_width(cfg.mla)


def init_longcat_params(key, cfg: LongcatConfig, dtype=jnp.float32
                        ) -> Dict[str, Any]:
    """Parameter pytree: every matrix N(0, 1/fan_in), gains ones, the
    router's correction bias (a buffer) zeros."""
    d, f, w = cfg.hidden_size, cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    h, rq, rkv = cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    n_out = cfg.n_routed_experts + cfg.zero_expert_num
    n_held = cfg.held[1]
    ks = iter(jax.random.split(key, 2 + cfg.num_layers * 24))

    def mat(*shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def ones(n):
        return jnp.ones((n,), dtype)

    def attn():
        return {"norm": ones(d), "W_qa": mat(d, rq), "q_norm": ones(rq),
                "W_qb": mat(rq, h * (nope + rope)),
                "W_kva": mat(d, rkv + rope), "kv_norm": ones(rkv),
                "W_kvb": mat(rkv, h * (nope + dv)), "W_o": mat(h * dv, d)}

    def ffn():
        return {"norm": ones(d), "Wg": mat(d, f), "Wu": mat(d, f),
                "Wd": mat(f, d)}

    return {
        "embed": jax.random.normal(next(ks), (cfg.vocab_size, d),
                                   jnp.float32).astype(dtype),
        "final_norm": ones(d), "head": mat(d, cfg.vocab_size),
        "layers": [{"attn": [attn(), attn()], "ffn": [ffn(), ffn()],
                    "moe": {"router": mat(d, n_out),
                            "bias": jnp.zeros((n_out,), dtype),
                            "Wg": mat(n_held, d, w), "Wu": mat(n_held, d, w),
                            "Wd": mat(n_held, w, d)}}
                   for _ in range(cfg.num_layers)]}


# ------------------------------------------------------------------ pieces


def _moe(m, u, cfg: LongcatConfig, valid):
    return moe_topk_share(
        m, u, top_k=cfg.moe_topk, n_routed=cfg.n_routed_experts,
        n_zero=cfg.zero_expert_num, scale=cfg.routed_scaling_factor,
        held=cfg.held, bias=m["bias"], valid=valid)


def _layer(lp, x, cfg: LongcatConfig, attend, valid):
    """One layer over tokens x (T, d); ``attend(sub, a, xn)`` is the path's
    attention for sub-layer ``sub`` (0 or 1). Returns (x', moe stats)."""
    eps = cfg.rms_norm_eps
    (a, b), (fa, fb) = lp["attn"], lp["ffn"]
    h = x + attend(0, a, _rms(x, a["norm"], eps))
    u = _rms(h, fa["norm"], eps)
    m, stats = _moe(lp["moe"], u, cfg, valid)
    h = h + _swiglu(fa, u)
    h = h + attend(1, b, _rms(h, b["norm"], eps))
    return h + _swiglu(fb, _rms(h, fb["norm"], eps)) + m, stats


# ---------------------------------------------------------------- programs


def longcat_prefill(params, ids, cfg: LongcatConfig, *, mask=None,
                    last=None):
    """Causal full-prompt forward of ONE prompt. ids: (1, T) int32; mask:
    optional (1, T), 1 = real token (end padding); ``last``: optional scalar
    position: the logits of that position alone are computed, ``(1, V)``,
    else all ``(1, T, V)``. Returns ``(logits, rows (2L, 1, 1, T, W),
    stats (L, count + 2))``: the cache rows of every attention sub-layer and
    the expert layers' statistics over the real tokens."""
    n, t = ids.shape
    if n != 1:
        raise ValueError("longcat_prefill takes one prompt a call")
    pos = jnp.arange(t)
    valid = jnp.ones((t,), bool) if mask is None else mask[0].astype(bool)
    m4 = valid[None, None, None, :]
    x = params["embed"][ids[0]]
    rows, stats = [], []

    def attend(sub, a, xn):
        out, row = mla.prefill_attention(a, xn, pos, m4, cfg.mla)
        rows.append(row)
        return out

    for lp in params["layers"]:
        x, st = _layer(lp, x, cfg, attend, valid)
        stats.append(st)
    if last is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    logits = _rms(x, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    logits = logits if last is not None else logits[None]
    return logits, jnp.stack(rows)[:, None, None], jnp.stack(stats)


def longcat_decode_step(params, kv_pages, tokens, positions, page_table,
                        seq_lens_incl, write_page, write_offset,
                        cfg: LongcatConfig):
    """One decode token for every slot against the latent paged pool
    ``(2L, 1, P, page, W)``, updated in place (donate it). The arguments are
    ``gpt_decode_step``'s. Each sub-layer scatters the token's row and then
    attends with the up-projections absorbed: ``q~_h = q_nope_h W_K,h^T``
    against the latents, ``o_h = (sum p c) W_V,h``. Returns ``(kv_pages,
    logits (S, V), stats (L, count + 2))``; the statistics count the active
    slots' tokens only."""
    valid = seq_lens_incl > positions
    x = params["embed"][tokens]
    stats = []
    for li, lp in enumerate(params["layers"]):

        def attend(sub, a, xn, li=li):
            nonlocal kv_pages
            kv_pages, out = mla.decode_attention(
                a, xn, positions, kv_pages, page_table, seq_lens_incl,
                write_page, write_offset, 2 * li + sub, cfg.mla)
            return out

        x, st = _layer(lp, x, cfg, attend, valid)
        stats.append(st)
    logits = _rms(x, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    return kv_pages, logits, jnp.stack(stats)


class LongcatModel:
    """Model handle: config + params, and what the serving engine asks a
    model for (``cache_rows``, ``serving_programs``)."""

    def __init__(self, cfg: LongcatConfig, seed: int = 0, dtype=jnp.float32,
                 params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.params = params if params is not None else init_longcat_params(
            jax.random.key(seed), cfg, dtype)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.params))

    def cache_rows(self) -> CacheRows:
        return longcat_cache_rows(self.cfg)

    def serving_programs(self) -> ServingPrograms:
        return longcat_programs(self.cfg)


def note_longcat_stats(cfg: LongcatConfig, stats, span=None, *,
                       decode_step: bool = False, tokens=None) -> None:
    """The expert layers' integers go to ``observe.note_moe``, with how the
    grouped products of a program over ``tokens`` token rows engaged."""
    observe.note_moe(
        stats, span, first_expert=cfg.held[0], decode_step=decode_step,
        grouped=tokens and grouped_path(
            stats, tokens, top_k=cfg.moe_topk,
            outputs=cfg.n_routed_experts + cfg.zero_expert_num))


def longcat_cache_rows(cfg: LongcatConfig) -> CacheRows:
    """One row a token an attention sub-layer, one side."""
    return CacheRows(layers=2 * cfg.num_layers, sides=1,
                     width=cache_row_width(cfg))


def longcat_programs(cfg: LongcatConfig) -> ServingPrograms:
    """The jittable programs, bound to ``cfg`` (needs no weights)."""

    def prefill(params, ids, prompt_len):
        mask = (jnp.arange(ids.shape[1]) < prompt_len)[None, :]
        logits, rows, stats = longcat_prefill(
            params, ids, cfg, mask=mask.astype(jnp.int32),
            last=prompt_len - 1)
        return logits, rows[:, :, 0], stats

    def decode_step(params, kv_pages, tokens, positions, page_table,
                    seq_lens_incl, write_page, write_offset):
        return longcat_decode_step(
            params, kv_pages, tokens, positions, page_table, seq_lens_incl,
            write_page, write_offset, cfg)

    return ServingPrograms(
        prefill=prefill, decode_step=decode_step,
        note_stats=functools.partial(note_longcat_stats, cfg))
