"""SDAR — a Qwen3-MoE-shaped decoder converted to generation by diffusion
over blocks (block diffusion), for generative serving.

A layer is pre-norm, as in Qwen3-MoE::

    h = RMSNorm(x);  q = rot(RMSNorm_head(W_q h)) (Hq, hd);  k = rot(RMSNorm_head(W_k h)) (Hkv, hd);  v = W_v h
    x += W_o attention(q, k, v)                  query head a reads key/value head a // (Hq / Hkv)
    n = RMSNorm(x);  s = softmax(W_r n) over every expert;  top-k of s, renormalised
    x += sum of the chosen experts' SwiGLU(n), each times its weight

with the rotation in half-split pairs ``(x[i], x[i + hd/2])``. A position
``t`` sees position ``j`` iff ``j // B <= t // B`` (block-causal, block
length ``B``): every earlier block and every position of its own block.

**Generation.** A prompt of length ``L`` is prefilled over its whole blocks,
positions ``0 .. P-1`` with ``P = (L // B) * B``. A block of positions ``b ..
b+B-1`` starts as the prompt's tail where it has one (the first block only;
those positions are fixed) and the mask token ``M`` elsewhere. A denoising
pass runs the layers over the block's B positions against the cached rows
``0 .. b-1`` and the block's own current rows, and unmasks the ONE masked
position of highest confidence ``softmax(logits)[x0]`` (``x0`` the argmax
with ``M``'s row excluded; ties to the lowest position): the static
low-confidence schedule at one token a pass. Once no mask is left, one more
pass over the clean block gives the K/V rows that are stored; its logits
are not computed. All passes of a block run in ONE program, a rolled loop
whose body is one pass (:func:`sdar_block_step`): the pool is read by every
pass and written once, after the loop, with the last (clean) pass's rows.

The cache is the paged pool of K/V rows (two sides, ``Hkv * hd`` values a
row): grouped heads over pages, read by the registry's
``block_decode_attention``. Weights and the products' inputs are in the
parameters' type (bfloat16 as served), every product accumulates in
float32, and the residual, norms, rotation, router, softmax, confidences
and logits are float32. No suffix prefill and no verify program: the engine
refuses ``prefix_pages``/``spec_k``, and sampled requests (the schedule is
greedy).
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
from deeplearning4j_tpu.models.mla import rms as _rms, rope as _rope
from deeplearning4j_tpu.models.served import CacheRows, ServingPrograms
from deeplearning4j_tpu.parallel.moe import grouped_path, moe_topk_share


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """JetLM/SDAR-30B-A3B-Chat under the source's own keys; the last three
    are not in its ``config.json`` (the family's released inference
    settings and its tokenizer: the benchmark's configuration file lists
    them as assumed). ``tiny()`` for tests."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    eos_token: int = 0
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669

    def __post_init__(self):
        if self.denoising_steps != self.block_length:
            raise ValueError(
                "only the static schedule at one token a pass is served "
                f"(denoising_steps {self.denoising_steps} != block_length "
                f"{self.block_length})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of the "
                             "key/value heads")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} outside "
                             f"the vocabulary of {self.vocab_size}")

    @property
    def max_position(self) -> int:
        return self.max_position_embeddings

    @staticmethod
    def tiny(**kw) -> "SdarConfig":
        """Test-sized, every mechanism kept: two layers, 4 query heads over
        2 key/value heads of 16, 8 experts top-2, blocks of 4."""
        d = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, num_experts=8,
                 num_experts_per_tok=2, max_position_embeddings=256,
                 mask_token_id=255)
        d.update(kw)
        return SdarConfig(**d)


def init_sdar_params(key, cfg: SdarConfig, dtype=jnp.float32
                     ) -> Dict[str, Any]:
    """Parameter pytree: every matrix N(0, 1/fan_in), gains ones."""
    d, w, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    ks = iter(jax.random.split(key, 2 + cfg.num_hidden_layers * 8))

    def mat(*shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dtype)

    def ones(k):
        return jnp.ones((k,), dtype)

    def layer():
        return {"attn": {"norm": ones(d), "W_q": mat(d, hq * hd),
                         "W_k": mat(d, hkv * hd), "W_v": mat(d, hkv * hd),
                         "q_norm": ones(hd), "k_norm": ones(hd),
                         "W_o": mat(hq * hd, d)},
                "moe": {"norm": ones(d), "router": mat(d, e),
                        "Wg": mat(e, d, w), "Wu": mat(e, d, w),
                        "Wd": mat(e, w, d)}}

    return {
        "embed": jax.random.normal(next(ks), (cfg.vocab_size, d),
                                   jnp.float32).astype(dtype),
        "final_norm": ones(d), "head": mat(d, cfg.vocab_size),
        "layers": [layer() for _ in range(cfg.num_hidden_layers)]}


# ------------------------------------------------------------------ layers


def _dot(x, w):
    """A product of the weights' type with a float32 result."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def attention_inputs(a, h, pos, cfg: SdarConfig):
    """What both paths make of normalised tokens h (N, d) at positions pos
    (N,): queries (N, Hq, hd) and keys (N, Hkv, hd), each normalised a head
    and rotated, and values (N, Hkv, hd). float32."""
    n = h.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    q = _rms(_dot(h, a["W_q"]).reshape(n, hq, hd), a["q_norm"], eps)
    k = _rms(_dot(h, a["W_k"]).reshape(n, hkv, hd), a["k_norm"], eps)
    q = _rope(q, pos, theta, pairing="half")
    k = _rope(k, pos, theta, pairing="half")
    return q, k, _dot(h, a["W_v"]).reshape(n, hkv, hd)


def _layer(lp, x, cfg: SdarConfig, attend, valid):
    """One layer over the residual x (N, d) float32; ``attend(a, h)`` is the
    path's attention over normalised tokens, (N, Hq * hd). Returns (x', the
    expert layer's statistics)."""
    a, m = lp["attn"], lp["moe"]
    eps = cfg.rms_norm_eps
    x = x + _dot(attend(a, _rms(x, a["norm"], eps)), a["W_o"])
    y, stats = moe_topk_share(
        m, _rms(x, m["norm"], eps), top_k=cfg.num_experts_per_tok,
        n_routed=cfg.num_experts, n_zero=0, scale=1.0,
        held=(0, cfg.num_experts), valid=valid, score="softmax",
        renormalise=cfg.norm_topk_prob)
    return x + y.astype(jnp.float32), stats


def _logits(params, x, cfg: SdarConfig):
    return _dot(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                params["head"])


# ---------------------------------------------------------------- programs


def sdar_prefill(params, ids, cfg: SdarConfig, prompt_len):
    """Block-causal forward of ONE padded prompt over its positions before
    ``prompt_len`` (a multiple of the block length: the prompt's whole
    blocks). ids: (1, T) int32. Returns ``(rows (L, 2, T, Hkv * hd) in the
    weights' type, stats (L, experts + 2))``: every layer's keys and
    values, and the expert layers' statistics over the real positions. The
    head is not run: a block model's first tokens come from its first
    block."""
    n, t = ids.shape
    if n != 1:
        raise ValueError("sdar_prefill takes one prompt a call")
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    pos = jnp.arange(t)
    valid = pos < prompt_len
    blk = pos // cfg.block_length
    see = (blk[None, :] <= blk[:, None]) & valid[None, :]     # (query, key)
    act = params["embed"].dtype
    x = params["embed"][ids[0]].astype(jnp.float32)
    rows, moe = [], []

    def attend(a, h):
        q, k, v = attention_inputs(a, h, pos, cfg)
        rows.append(jnp.stack([k.reshape(t, -1), v.reshape(t, -1)]
                              ).astype(act))
        with jax.named_scope("block_prefill_attention"):
            qg = q.reshape(t, hkv, hq // hkv, hd)
            s = jnp.einsum("tcgd,ucd->cgtu", qg, k) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
            return jnp.einsum("cgtu,ucd->tcgd", p, v).reshape(t, hq * hd)

    for lp in params["layers"]:
        x, st = _layer(lp, x, cfg, attend, valid)
        moe.append(st)
    return jnp.stack(rows), jnp.stack(moe)


def _unmask(params, x, toks, when, active, n, cfg: SdarConfig):
    """One pass's pick: at every masked position of an active slot, ``x0``
    (the argmax with the mask's row excluded) and its log-confidence
    ``logits[x0] - logsumexp(logits)``, float32; the ONE masked position of
    highest confidence a slot takes its ``x0`` and the pass number ``n``."""
    s_n, b = toks.shape
    m_id = cfg.mask_token_id
    logits = _logits(params, x, cfg)                              # (S*B, V)
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.where(jnp.arange(logits.shape[-1]) == m_id, -jnp.inf, logits)
    x0 = jnp.argmax(pick, axis=-1).astype(toks.dtype).reshape(s_n, b)
    conf = (jnp.max(pick, axis=-1) - lse).reshape(s_n, b)
    masked = (toks == m_id) & active[:, None]
    at = jnp.argmax(jnp.where(masked, conf, -jnp.inf), axis=-1)   # lowest tie
    hit = (jnp.arange(b)[None, :] == at[:, None]) & masked
    return jnp.where(hit, x0, toks), jnp.where(hit, n, when)


def sdar_block_step(params, kv_pages, block_init, starts, page_table,
                    write_page, write_offset, active, cfg: SdarConfig):
    """One block for every slot against the paged pool ``(L, 2, P, page,
    Hkv * hd)``, updated in place (donate it). ``block_init`` (S, B): the
    block's tokens as it starts (``M`` where masked; the prompt's tail where
    a slot's first block has one); ``starts`` (S,): the block's first
    position, which is the rows cached before it; ``write_page`` /
    ``write_offset`` (S, B): where the block's rows go (the trash page for a
    slot that is not ``active``).

    The passes run in a rolled loop whose body is one forward pass over the
    S x B positions: while an active slot holds a mask, a denoising pass;
    then one store pass over the clean blocks, after which the loop ends
    and its rows (every layer's keys and values of the clean blocks) are
    written into the pool. Returns ``(kv_pages, tokens (S, B), unmask pass
    (S, B) int32 (-1 where fixed), passes run (the store pass included),
    stats (L, experts + 2))``: the statistics summed over the passes, of the
    active slots' rows."""
    from deeplearning4j_tpu.ops import exec_op

    s_n, b = block_init.shape
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    n_l = cfg.num_hidden_layers
    act = params["embed"].dtype
    pos = (starts[:, None] + jnp.arange(b)[None, :]).reshape(-1)
    valid = jnp.repeat(active, b)

    def forward(toks):
        x = params["embed"][toks.reshape(-1)].astype(jnp.float32)
        rows, moe = [], []
        for li, lp in enumerate(params["layers"]):

            def attend(a, h, li=li):
                q, k, v = attention_inputs(a, h, pos, cfg)
                k, v = (z.astype(act).reshape(s_n, b, hkv, hd) for z in (k, v))
                rows.append(jnp.stack([k.reshape(s_n, b, -1),
                                       v.reshape(s_n, b, -1)]))
                o = exec_op("block_decode_attention",
                            q.astype(act).reshape(s_n, b, hq, hd), k, v,
                            kv_pages, page_table, starts, layer=li,
                            scale=1.0 / math.sqrt(hd))
                return o.reshape(s_n * b, hq * hd)

            x, st = _layer(lp, x, cfg, attend, valid)
            moe.append(st)
        return x, jnp.stack(rows), jnp.stack(moe)

    def one_pass(c):
        toks, when, _, moe, n, _ = c
        x, rows, st = forward(toks)
        dirty = jnp.any((toks == cfg.mask_token_id) & active[:, None])
        toks, when = jax.lax.cond(
            dirty, lambda: _unmask(params, x, toks, when, active, n, cfg),
            lambda: (toks, when))
        return toks, when, rows, moe + st, n + 1, ~dirty

    init = (block_init, jnp.full((s_n, b), -1, jnp.int32),
            jnp.zeros((n_l, 2, s_n, b, hkv * hd), act),
            jnp.zeros((n_l, cfg.num_experts + 2), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), bool))
    with jax.named_scope("block_denoise"):
        toks, when, rows, moe, passes, _ = jax.lax.while_loop(
            lambda c: ~c[5], one_pass, init)
    with jax.named_scope("block_store"):
        # one scatter a layer and side, the form every decode write takes
        for li in range(n_l):
            for side in (0, 1):
                kv_pages = kv_pages.at[li, side, write_page,
                                       write_offset].set(rows[li, side])
    return kv_pages, toks, when, passes, moe


def note_sdar_stats(cfg: SdarConfig, stats, span=None, *,
                    decode_step: bool = False, tokens=None) -> None:
    """The expert layers' integers (summed over a block step's passes) go
    to ``observe.note_moe`` with how the grouped products of a pass over
    ``tokens`` token rows engaged."""
    observe.note_moe(
        stats, span, first_expert=0, decode_step=decode_step,
        grouped=tokens and grouped_path(
            stats, tokens, top_k=cfg.num_experts_per_tok,
            outputs=cfg.num_experts))


class SdarModel:
    """Model handle: config + params, and what the serving engine asks a
    model for (``cache_rows``, ``serving_programs``)."""

    def __init__(self, cfg: SdarConfig, seed: int = 0, dtype=jnp.float32,
                 params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.params = params if params is not None else init_sdar_params(
            jax.random.key(seed), cfg, dtype)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.params))

    def cache_rows(self) -> CacheRows:
        return sdar_cache_rows(self.cfg)

    def serving_programs(self) -> ServingPrograms:
        return sdar_programs(self.cfg)


def sdar_cache_rows(cfg: SdarConfig) -> CacheRows:
    """A key row and a value row a token a layer, heads merged."""
    return CacheRows(layers=cfg.num_hidden_layers, sides=2,
                     width=cfg.num_key_value_heads * cfg.head_dim)


def sdar_programs(cfg: SdarConfig) -> ServingPrograms:
    """The jittable programs, bound to ``cfg`` (needs no weights): a block
    model's, a block of ``block_length`` positions a step."""

    def prefill(params, ids, prompt_len):
        return sdar_prefill(params, ids, cfg, prompt_len)

    def decode_step(params, kv_pages, block_init, starts, page_table,
                    write_page, write_offset, active):
        return sdar_block_step(params, kv_pages, block_init, starts,
                               page_table, write_page, write_offset, active,
                               cfg)

    return ServingPrograms(prefill=prefill, decode_step=decode_step,
                           note_stats=functools.partial(note_sdar_stats, cfg),
                           block=cfg.block_length)
