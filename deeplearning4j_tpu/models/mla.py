"""Latent attention (MLA): the pieces every served latent-attention model
calls (``models/longcat.py``, ``models/xing.py``); its norm, rotation and
gated feed-forward also serve ``models/brumby.py``.

Queries go through a low-rank ``q_rank`` bottleneck, keys and values through
ONE shared latent of ``kv_rank`` values a token plus a rotary key of ``rope``
values shared by all heads. What a token leaves in the cache is one row an
attention (sub-)layer: ``[RMS(c) * s_kv | rotated k_r | dead lanes]``
(:func:`cache_row_width`). :func:`prefill_attention` materialises K and V
from the latent and runs the registry's ``dot_product_attention``;
:func:`decode_attention` scatters the token's row into the latent paged pool,
absorbs the up-projections into the query and the output and runs the
registry's ``latent_decode_attention`` against the pool where it lies.

A model says what its attention is with :class:`MlaDims`; YaRN
(:class:`Yarn`) is an argument of the rotation and of the softmax's scale,
``None`` for a model without it.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Yarn(NamedTuple):
    """YaRN's ``rope_scaling`` under the source's own keys."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


class MlaDims(NamedTuple):
    """What the attention needs of a configuration."""

    heads: int
    hidden: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    eps: float
    theta: float
    scale_q: bool = False       # queries times sqrt(hidden / q_rank)
    scale_kv: bool = False      # the latent times sqrt(hidden / kv_rank)
    yarn: Optional[Yarn] = None

    @property
    def yarn_scale(self) -> float:
        """What YaRN multiplies the softmax's scale by: ``mscale(factor,
        mscale_all_dim)^2``; 1 for a model without it."""
        if self.yarn is None:
            return 1.0
        return yarn_mscale(self.yarn.factor, self.yarn.mscale_all_dim) ** 2

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5`` times :attr:`yarn_scale`."""
        return self.yarn_scale / math.sqrt(self.nope + self.rope)


def dims_of(cfg, **more) -> MlaDims:
    """From a configuration under the source's own keys (``hidden_size``,
    ``num_attention_heads``, ``q_lora_rank``, ...); ``more``: what the source
    names otherwise or not at all (``scale_q``, ``scale_kv``, ``yarn``)."""
    return MlaDims(
        heads=cfg.num_attention_heads, hidden=cfg.hidden_size,
        q_rank=cfg.q_lora_rank, kv_rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        **more)


def cache_row_width(m: MlaDims) -> int:
    """Latent and rotary key, rounded up to whole 128-lane tiles: 576 values
    are 4.5 tiles, which the device would pad (and copy the pool to do so);
    640 with 64 dead lanes it keeps row-major (tests/test_tpu_compile.py)."""
    return -(-(m.kv_rank + m.rope) // 128) * 128


# ------------------------------------------------------------------ pieces


def rms(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_keep(n: int, theta: float, yarn: Yarn) -> np.ndarray:
    """For each of a rotation's ``n // 2`` frequencies, the share that stays
    as published (1: a pair that turns more than ``beta_fast`` times in the
    original positions) against interpolated by ``factor`` (0: fewer than
    ``beta_slow`` turns); a linear ramp between the two correction
    dimensions."""

    def correction_dim(turns):
        return (n * math.log(yarn.original_max_position_embeddings
                             / (turns * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), n - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(n // 2, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    return (1.0 - ramp).astype(np.float32)


def inv_freq(n: int, theta: float, yarn: Optional[Yarn] = None):
    """The ``n // 2`` angular frequencies of a rotation, float32."""
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    if yarn is None:
        return inv
    keep = yarn_keep(n, theta, yarn)
    return inv / yarn.factor * (1.0 - keep) + inv * keep


def rope(x, pos, theta, yarn: Optional[Yarn] = None,
         pairing: str = "interleaved"):
    """Rotate pairs of the last axis by ``pos * inv_freq[i]``, in float32:
    the interleaved pairs (x[2i], x[2i+1]) (the DeepSeek convention), or
    with ``pairing="half"`` the pairs (x[i], x[i + n/2]) (the Llama and Qwen
    convention). x: (..., n) with leading axes those of ``pos`` and then any
    others. With ``yarn`` the frequencies are YaRN's and the cosines and
    sines take its ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``."""
    n = x.shape[-1]
    inv = inv_freq(n, theta, yarn)
    ang = pos.astype(jnp.float32)[..., None] * inv
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - pos.ndim - 1) + (n // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        size = (yarn_mscale(yarn.factor, yarn.mscale)
                / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if size != 1.0:
            cos, sin = cos * size, sin * size
    if pairing == "half":
        x32 = x.astype(jnp.float32)
        lo, hi = x32[..., :n // 2], x32[..., n // 2:]
        return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                               axis=-1).astype(x.dtype)
    if pairing != "interleaved":
        raise ValueError(f"unknown rotary pairing {pairing!r}")
    pair = x.astype(jnp.float32).reshape(x.shape[:-1] + (n // 2, 2))
    even, odd = pair[..., 0], pair[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def swiglu(f, x):
    with jax.named_scope("dense_ffn"):
        return (jax.nn.silu(x @ f["Wg"]) * (x @ f["Wu"])) @ f["Wd"]


def mla_inputs(a, x, pos, m: MlaDims):
    """What both attention paths share. x: (..., d) normalised, pos: (...).
    Returns the heads' queries ``q_nope (..., H, nope)``, ``q_rope (..., H,
    rope)`` (rotated) and the token's cache row ``(..., W)``: the normalised,
    scaled latent, the rotated shared key, dead lanes."""
    h, nope, rp, rkv = m.heads, m.nope, m.rope, m.kv_rank
    s_q = math.sqrt(m.hidden / m.q_rank) if m.scale_q else 1.0
    s_kv = math.sqrt(m.hidden / rkv) if m.scale_kv else 1.0
    c_q = rms(x @ a["W_qa"], a["q_norm"], m.eps)
    q = (c_q @ a["W_qb"]).reshape(x.shape[:-1] + (h, nope + rp)) * s_q
    q = q.astype(x.dtype)
    ckr = x @ a["W_kva"]
    c = (rms(ckr[..., :rkv], a["kv_norm"], m.eps).astype(
        jnp.float32) * s_kv).astype(x.dtype)
    k_r = rope(ckr[..., rkv:], pos, m.theta, m.yarn)
    dead = cache_row_width(m) - rkv - rp
    row = jnp.concatenate(
        [c, k_r, jnp.zeros(x.shape[:-1] + (dead,), x.dtype)], axis=-1)
    return q[..., :nope], rope(q[..., nope:], pos, m.theta, m.yarn), row


# --------------------------------------------------------------- attention


def prefill_attention(a, xn, pos, mask4, m: MlaDims):
    """Causal attention of ONE prompt with keys and values materialised from
    the latent. xn: (T, d) normalised, pos: (T,), mask4: (1, 1, 1, T) bool,
    the real tokens. Returns ``(out (T, d), rows (T, W))``."""
    from deeplearning4j_tpu.ops import exec_op

    t = xn.shape[0]
    h, rkv, nope, dv, rp = m.heads, m.kv_rank, m.nope, m.v_dim, m.rope
    q_nope, q_rope, row = mla_inputs(a, xn, pos, m)
    extra = m.yarn_scale   # the registry's op scales by the width alone
    with jax.named_scope("mla_prefill_attention"):
        kv = (row[:, :rkv] @ a["W_kvb"]).reshape(t, h, nope + dv)
        k_r = jnp.broadcast_to(row[:, None, rkv:rkv + rp], (t, h, rp))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
        heads_first = lambda z: z.astype(jnp.float32).transpose(  # noqa: E731
            1, 0, 2)[None]
        q = heads_first(q) if extra == 1.0 else heads_first(q) * extra
        out = exec_op("dot_product_attention", q, heads_first(k),
                      heads_first(kv[..., nope:]), mask4,
                      scaled=True, causal=True)   # softmax in float32
        out = out[0].transpose(1, 0, 2).reshape(t, h * dv)
    return out.astype(xn.dtype) @ a["W_o"], row


def decode_attention(a, xn, positions, kv_pages, page_table, seq_lens_incl,
                     write_page, write_offset, layer: int, m: MlaDims):
    """One decode token a slot against the latent paged pool ``(L, 1, P,
    page, W)``: scatter the token's row into attention (sub-)layer
    ``layer``, then attend with the up-projections absorbed: ``q~_h =
    q_nope_h W_K,h^T`` against the latents, ``o_h = (sum p c) W_V,h``.
    xn: (S, d) normalised. Returns ``(kv_pages, out (S, d))``."""
    from deeplearning4j_tpu.ops import exec_op

    h, rkv, nope, dv = m.heads, m.kv_rank, m.nope, m.v_dim
    q_nope, q_rope, row = mla_inputs(a, xn, positions, m)
    kv_pages = kv_pages.at[layer, 0, write_page, write_offset].set(row)
    w_kvb = a["W_kvb"].reshape(rkv, h, nope + dv)
    q_abs = jnp.einsum("shn,rhn->shr", q_nope, w_kvb[..., :nope])
    lat = exec_op("latent_decode_attention", q_abs, q_rope, kv_pages,
                  page_table, seq_lens_incl, layer=layer,
                  scale=m.softmax_scale, value_width=rkv)
    out = jnp.einsum("shr,rhv->shv", lat, w_kvb[..., nope:])
    return kv_pages, out.reshape(xn.shape[0], h * dv) @ a["W_o"]
