"""Brumby — a Qwen3-shaped decoder whose every attention layer is a power
retention layer (degree 2, gated, normalised, grouped heads), for generative
serving.

A layer is pre-norm: ``x += W_o retention(RMSNorm(x)); x += SwiGLU(RMSNorm(
x))``. Retention projects the normalised token to ``num_attention_heads``
queries and ``num_key_value_heads`` keys and values of ``head_dim``, gives
queries and keys a per-head gained RMSNorm and the rotation (pairs ``(x[i],
x[i + head_dim/2])``), and a log-decay ``g = log sigmoid(W_g h + b_g)`` a
key/value head. Query head ``a`` reads key/value head ``a // group``::

    w[t, j] = ((q_t . k_j) / sqrt d)^2 * exp(g_{j+1} + ... + g_t)     (j <= t)
    y_t     = sum_j w[t, j] v_j / (sum_j w[t, j] + eps)

which ``ops/pallas_retention.py`` computes as that quadratic form over a
prompt (``power_retention_prefill``) and as a recurrence over a state ``S
(rows of phi, dv, d)`` and a normaliser ``z`` a key/value head when decoding
(``power_retention_decode``). **What a sequence leaves in the cache is that
state, a fixed size a slot whatever its length** (``models/served.py``
``SlotState``), float32: the first served model without rows a token.

Weights and the matrices' inputs are in the parameters' type (bfloat16 as
served); the residual, the norms, the rotation, ``g``, ``phi``, the state,
the normaliser and the logits are float32. No suffix prefill and no verify
program (a prefix would be a state snapshot, a rejected draft a state to
roll back: docs/SERVING.md): the engine refuses ``prefix_pages``/``spec_k``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.models.mla import rms as _rms, rope as _rope, \
    swiglu as _swiglu
from deeplearning4j_tpu.models.served import ServingPrograms, SlotState
from deeplearning4j_tpu.ops.pallas_retention import phi_rows


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """manifestai/Brumby-14B-Base under the source's own keys; the last
    three are not in its ``config.json`` (the family's convention: the
    benchmark's configuration file lists them as assumed). ``tiny()`` for
    tests."""

    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    eos_token: int = 0
    retention_degree: int = 2
    retention_eps: float = 1e-6
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.retention_degree != 2:
            raise ValueError("only degree-2 power retention is implemented "
                             f"(got {self.retention_degree})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of the "
                             "key/value heads")

    @property
    def max_position(self) -> int:
        return self.max_position_embeddings

    @staticmethod
    def tiny(**kw) -> "BrumbyConfig":
        """Test-sized, every mechanism kept: two layers, 4 query heads over
        2 key/value heads of 16 (136 symmetric products a head)."""
        d = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16,
                 max_position_embeddings=256)
        d.update(kw)
        return BrumbyConfig(**d)


def init_brumby_params(key, cfg: BrumbyConfig, dtype=jnp.float32,
                       gate_bias: float = 4.0) -> Dict[str, Any]:
    """Parameter pytree: every matrix N(0, 1/fan_in), gains ones, the gate's
    bias ``gate_bias`` (a decay near ``sigmoid(gate_bias)``)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
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
                         "W_g": mat(d, hkv),
                         "b_g": jnp.full((hkv,), gate_bias, dtype),
                         "q_norm": ones(hd), "k_norm": ones(hd),
                         "W_o": mat(hq * hd, d)},
                "ffn": {"norm": ones(d), "Wg": mat(d, f), "Wu": mat(d, f),
                        "Wd": mat(f, d)}}

    return {
        "embed": jax.random.normal(next(ks), (cfg.vocab_size, d),
                                   jnp.float32).astype(dtype),
        "final_norm": ones(d), "head": mat(d, cfg.vocab_size),
        "layers": [layer() for _ in range(cfg.num_hidden_layers)]}


# ------------------------------------------------------------------ layers


def _dot(x, w):
    """A product of the weights' type with a float32 result."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def retention_inputs(a, h, pos, cfg: BrumbyConfig):
    """What both paths make of normalised tokens h (N, d) at positions pos
    (N,): queries (N, Hq, hd) and keys (N, Hkv, hd), each normalised a head
    and rotated, values (N, Hkv, hd) and the log-decays (N, Hkv). float32."""
    n = h.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    q = _rms(_dot(h, a["W_q"]).reshape(n, hq, hd), a["q_norm"], eps)
    k = _rms(_dot(h, a["W_k"]).reshape(n, hkv, hd), a["k_norm"], eps)
    q = _rope(q, pos, theta, pairing="half")
    k = _rope(k, pos, theta, pairing="half")
    v = _dot(h, a["W_v"]).reshape(n, hkv, hd)
    g = jax.nn.log_sigmoid(_dot(h, a["W_g"]) + a["b_g"].astype(jnp.float32))
    return q, k, v, g


def _layer(lp, x, cfg: BrumbyConfig, retain):
    """One layer over the residual x (N, d) float32; ``retain(a, h)`` is the
    path's retention over normalised tokens, (N, Hq * hd) float32."""
    a, f = lp["attn"], lp["ffn"]
    act = a["W_o"].dtype
    x = x + _dot(retain(a, _rms(x, a["norm"], cfg.rms_norm_eps)), a["W_o"])
    ff = _swiglu(f, _rms(x, f["norm"], cfg.rms_norm_eps).astype(act))
    return x + ff.astype(jnp.float32)


def _logits(params, x, cfg: BrumbyConfig):
    return _dot(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                params["head"])


def _statistics(den_min, absmax, decays):
    """What a program hands out beside its logits: the smallest normaliser,
    the largest ``|S|`` entry touched and the mean decay, over the program's
    layers and the real tokens (active slots)."""
    total, count = (sum(x) for x in zip(*decays))
    return {"retention": jnp.stack([
        jnp.min(jnp.stack(den_min)), jnp.max(jnp.stack(absmax)),
        total / jnp.maximum(count, 1.0)])}


def _decay_sum(g, seen):
    """(sum of exp(g) over the seen tokens, how many values that is)."""
    w = seen.astype(jnp.float32)[:, None]
    return jnp.sum(jnp.exp(g) * w), jnp.sum(w) * g.shape[1]


# ---------------------------------------------------------------- programs


def brumby_prefill(params, ids, cfg: BrumbyConfig, *, mask=None, last=None):
    """Causal full-prompt forward of ONE prompt by the quadratic form. ids:
    (1, T) int32; mask: optional (1, T), 1 = real token (end padding);
    ``last``: optional scalar position: the logits of that position alone,
    ``(1, V)``, else all ``(1, T, V)``. Returns ``(logits float32, state,
    stats)``: the state the last real position leaves, ``{"S": (L, Hkv,
    rows, hd, hd), "z": (L, Hkv, rows, hd)}`` float32."""
    from deeplearning4j_tpu.ops import exec_op

    n, t = ids.shape
    if n != 1:
        raise ValueError("brumby_prefill takes one prompt a call")
    pos = jnp.arange(t)
    valid = jnp.ones((t,), bool) if mask is None else mask[0].astype(bool)
    x = params["embed"][ids[0]].astype(jnp.float32)
    states, norms, den_min, absmax, decays = [], [], [], [], []

    def retain(a, h):
        q, k, v, g = retention_inputs(a, h, pos, cfg)
        y, state, norm, den = exec_op("power_retention_prefill", q, k, v, g,
                                      valid, eps=cfg.retention_eps)
        states.append(state)
        norms.append(norm)
        den_min.append(jnp.min(jnp.where(valid[:, None], den, jnp.inf)))
        absmax.append(jnp.max(jnp.abs(state)))
        decays.append(_decay_sum(g, valid))
        return y.reshape(t, -1)

    for lp in params["layers"]:
        x = _layer(lp, x, cfg, retain)
    if last is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=0)
    logits = _logits(params, x, cfg)
    logits = logits if last is not None else logits[None]
    return (logits, {"S": jnp.stack(states), "z": jnp.stack(norms)},
            _statistics(den_min, absmax, decays))


def brumby_decode_step(params, pool, tokens, positions, active,
                       cfg: BrumbyConfig):
    """One decode token for every slot against the pool of states ``{"S":
    (slots, L, Hkv, rows, hd, hd), "z": (slots, L, Hkv, rows, hd)}``,
    updated in place (donate it); a slot that is not ``active`` keeps its
    state. Returns ``(pool, logits (S, V) float32, stats)``; the statistics
    count the active slots only."""
    from deeplearning4j_tpu.ops import exec_op

    x = params["embed"][tokens].astype(jnp.float32)
    state, norm = pool["S"], pool["z"]
    den_min, absmax, decays = [], [], []
    for li, lp in enumerate(params["layers"]):

        def retain(a, h, li=li):
            nonlocal state, norm
            q, k, v, g = retention_inputs(a, h, positions, cfg)
            state, norm, y, den, amax = exec_op(
                "power_retention_decode", state, norm, q, k, v, g, active,
                layer=li, eps=cfg.retention_eps)
            den_min.append(jnp.min(jnp.where(active[:, None], den, jnp.inf)))
            absmax.append(amax)
            decays.append(_decay_sum(g, active))
            return y.reshape(x.shape[0], -1)

        x = _layer(lp, x, cfg, retain)
    return ({"S": state, "z": norm}, _logits(params, x, cfg),
            _statistics(den_min, absmax, decays))


def note_brumby_stats(stats, span=None, *, decode_step: bool = False,
                      tokens=None) -> None:
    """The one part of a program's statistics: the retention layers' three
    numbers go to ``observe.note_retention``, under the form of the function
    the program ran (the state's recurrence when decoding, the quadratic
    form over a prompt)."""
    den_min, absmax, decay = (float(x) for x in stats["retention"])
    observe.note_retention(den_min, absmax, decay, span,
                           form="state" if decode_step else "quadratic")


class BrumbyModel:
    """Model handle: config + params, and what the serving engine asks a
    model for (``cache_rows``, ``serving_programs``)."""

    def __init__(self, cfg: BrumbyConfig, seed: int = 0, dtype=jnp.float32,
                 params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.params = params if params is not None else init_brumby_params(
            jax.random.key(seed), cfg, dtype)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.params))

    def cache_rows(self) -> SlotState:
        return brumby_slot_state(self.cfg)

    def serving_programs(self) -> ServingPrograms:
        return brumby_programs(self.cfg)


def brumby_slot_state(cfg: BrumbyConfig) -> SlotState:
    """A slot's state: every layer's and key/value head's ``S`` and ``z``,
    in the state's own type (not the weights')."""
    hd = cfg.head_dim
    per_head = (cfg.num_hidden_layers, cfg.num_key_value_heads, phi_rows(hd))
    return SlotState(arrays={"S": (per_head + (hd, hd), cfg.state_dtype),
                             "z": (per_head + (hd,), cfg.state_dtype)})


def brumby_programs(cfg: BrumbyConfig) -> ServingPrograms:
    """The jittable programs, bound to ``cfg`` (needs no weights)."""

    def prefill(params, ids, prompt_len):
        mask = (jnp.arange(ids.shape[1]) < prompt_len)[None, :]
        return brumby_prefill(params, ids, cfg, mask=mask.astype(jnp.int32),
                              last=prompt_len - 1)

    def decode_step(params, pool, tokens, positions, active):
        return brumby_decode_step(params, pool, tokens, positions, active,
                                  cfg)

    return ServingPrograms(prefill=prefill, decode_step=decode_step,
                           note_stats=note_brumby_stats)
