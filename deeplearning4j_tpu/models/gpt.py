"""GPT — decoder-only causal transformer for generative serving.

The generative tier of the model zoo (ROADMAP item 2): the block layout —
post-LN residual attention + FFN with the same param names (Wq/bq … W2/b2,
ln_gamma/ln_beta) — is ``models/bert.py``'s encoder block reused verbatim,
so TP sharding rules (`parallel.mesh.DEFAULT_TP_RULES`) and checkpoint
mapping apply unchanged. What differs is the attention pattern and the
execution split the serving engine needs:

* **prefill** (:func:`gpt_prefill`): the whole prompt in ONE causal
  attention pass through the registry's ``dot_product_attention`` — the
  Pallas flash platform helper fires on TPU above the ``flash_min_t()``
  crossover, the XLA path below it — returning per-position logits AND the
  per-layer K/V the serving engine scatters into its paged cache.
* **decode** (:func:`gpt_decode_step`): ONE token per sequence against the
  block-paged KV cache via the registry's ``paged_decode_attention``
  (Pallas on TPU, gather fallback elsewhere). All shapes are functions of
  the slot capacity, never of the number of active sequences, so the
  serving loop compiles exactly once (docs/SERVING.md).
* **verify** (:func:`gpt_verify`): the speculative-decoding target pass
  (docs/SERVING.md § Speculative decoding) — ``K+1`` proposed tokens per
  sequence in ONE forward against the paged cache, scoring every draft
  proposal at once. Shapes depend on ``(max_slots, spec_k, page
  geometry)`` only, so speculation joins the compile-once family.

Draft/target pairing: :func:`draft_config_for` builds the GPT-tiny-sized
draft config that shares a target's vocab/eos/positions — the pairing the
zoo exposes as ``models.GPT(preset).init_draft()``.

Tied embeddings: logits project through ``embeddings.word.T`` (the BERT MLM
head convention), so the checkpoint is exactly the param pytree.
"""

from __future__ import annotations

import dataclasses
import json
import math
import zipfile
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.bert import _layer_norm
from deeplearning4j_tpu.models.served import CacheRows, ServingPrograms


@dataclasses.dataclass(frozen=True)
class GptConfig:
    """GPT-2-small defaults; ``tiny()`` for tests and CPU smoke serving."""

    vocab_size: int = 50257
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    intermediate: int = 3072
    max_position: int = 1024
    layer_norm_eps: float = 1e-5
    eos_token: int = 0

    @staticmethod
    def base(**kw) -> "GptConfig":
        return GptConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "GptConfig":
        """Test-sized config (mirrors BertConfig.tiny)."""
        d = dict(vocab_size=256, hidden=64, layers=2, heads=4,
                 intermediate=128, max_position=128)
        d.update(kw)
        return GptConfig(**d)

    # ------------------------------------------------------------- round-trip
    def to_json(self) -> str:
        return json.dumps({"@type": "GptConfig",
                           **dataclasses.asdict(self)}, indent=1)

    @staticmethod
    def from_json(s: str) -> "GptConfig":
        d = json.loads(s)
        d.pop("@type", None)
        return GptConfig(**d)


def draft_config_for(cfg: GptConfig, **overrides) -> "GptConfig":
    """The paired DRAFT config for speculative decoding against ``cfg``
    (docs/SERVING.md § Speculative decoding): GPT-tiny-sized transformer
    dims, but vocab_size/eos_token/max_position copied from the target —
    draft proposals are target token ids at target positions, so those
    three must agree (the serving engine validates them again at
    construction). ``overrides`` widen/narrow the draft dims."""
    d = dict(vocab_size=cfg.vocab_size, max_position=cfg.max_position,
             eos_token=cfg.eos_token, hidden=64, layers=2, heads=4,
             intermediate=128)
    d.update(overrides)
    return GptConfig(**d)


def init_gpt_params(key, cfg: GptConfig, dtype=jnp.float32) -> Dict[str, Any]:
    """Parameter pytree; block layout and names identical to
    ``init_bert_params`` encoder blocks (attn Wq…Wo + ln, ffn W1/W2 + ln)."""
    ks = iter(jax.random.split(key, 4 + cfg.layers * 16))

    def nrm(shape):
        return 0.02 * jax.random.normal(next(ks), shape, dtype)

    p: Dict[str, Any] = {
        "embeddings": {
            "word": nrm((cfg.vocab_size, cfg.hidden)),
            "position": nrm((cfg.max_position, cfg.hidden)),
            "ln_gamma": jnp.ones((cfg.hidden,), dtype),
            "ln_beta": jnp.zeros((cfg.hidden,), dtype),
        },
        "blocks": [],
    }
    for _ in range(cfg.layers):
        p["blocks"].append({
            "attn": {
                "Wq": nrm((cfg.hidden, cfg.hidden)), "bq": jnp.zeros((cfg.hidden,), dtype),
                "Wk": nrm((cfg.hidden, cfg.hidden)), "bk": jnp.zeros((cfg.hidden,), dtype),
                "Wv": nrm((cfg.hidden, cfg.hidden)), "bv": jnp.zeros((cfg.hidden,), dtype),
                "Wo": nrm((cfg.hidden, cfg.hidden)), "bo": jnp.zeros((cfg.hidden,), dtype),
                "ln_gamma": jnp.ones((cfg.hidden,), dtype),
                "ln_beta": jnp.zeros((cfg.hidden,), dtype),
            },
            "ffn": {
                "W1": nrm((cfg.hidden, cfg.intermediate)),
                "b1": jnp.zeros((cfg.intermediate,), dtype),
                "W2": nrm((cfg.intermediate, cfg.hidden)),
                "b2": jnp.zeros((cfg.hidden,), dtype),
                "ln_gamma": jnp.ones((cfg.hidden,), dtype),
                "ln_beta": jnp.zeros((cfg.hidden,), dtype),
            },
        })
    return p


def _ffn(blk, x, eps):
    f = blk["ffn"]
    hdn = jax.nn.gelu(x @ f["W1"] + f["b1"])
    return _layer_norm(x + hdn @ f["W2"] + f["b2"],
                       f["ln_gamma"], f["ln_beta"], eps)


def gpt_prefill(params, ids, cfg: GptConfig, *, mask=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal full-prompt forward.

    ids: (N, T) int32; mask: optional (N, T) 1=real token (end padding).
    Returns ``(logits (N, T, V), kv (L, 2, N, T, H*Dh))`` — the per-layer
    keys/values as the paged pool holds them (token-major, heads merged:
    the projections' own rows), for the serving engine to write as pages.
    """
    from deeplearning4j_tpu.ops import exec_op

    emb = params["embeddings"]
    n, t = ids.shape
    if t > cfg.max_position:
        # the position gather would silently CLAMP indices past
        # max_position (every excess token reusing the last embedding) —
        # reject instead of returning quietly-wrong logits
        raise ValueError(
            f"sequence length {t} exceeds max_position={cfg.max_position}")
    h, dh = cfg.heads, cfg.hidden // cfg.heads
    x = emb["word"][ids] + emb["position"][jnp.arange(t)][None]
    x = _layer_norm(x, emb["ln_gamma"], emb["ln_beta"], cfg.layer_norm_eps)

    def split(a):  # (N, T, E) -> (N, H, T, Dh)
        return a.reshape(n, t, h, dh).transpose(0, 2, 1, 3)

    m4 = None if mask is None else mask[:, None, None, :].astype(bool)
    kvs = []
    for blk in params["blocks"]:
        a = blk["attn"]
        q = split(x @ a["Wq"] + a["bq"])
        k_row = x @ a["Wk"] + a["bk"]
        v_row = x @ a["Wv"] + a["bv"]
        kvs.append(jnp.stack([k_row, v_row]))  # (2, N, T, H*Dh)
        k, v = split(k_row), split(v_row)
        out = exec_op("dot_product_attention", q, k, v, m4, scaled=True,
                      causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(n, t, cfg.hidden)
        x = _layer_norm(x + out @ a["Wo"] + a["bo"],
                        a["ln_gamma"], a["ln_beta"], cfg.layer_norm_eps)
        x = _ffn(blk, x, cfg.layer_norm_eps)
    logits = x @ emb["word"].T
    return logits, jnp.stack(kvs)


def gpt_prefill_suffix(params, ids, prefix_kv, prefix_len, suffix_len,
                       cfg: GptConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Suffix-only prefill against a cached prefix (the radix prefix
    cache's fast path, docs/SERVING.md § Radix prefix cache).

    ids: (1, B) int32 — the prompt's UNCACHED tail, zero-padded to the
    engine's suffix bucket; prefix_kv: (L, 2, Tpre, H*Dh) — the cached
    prefix K/V gathered from the paged pool (positions >= ``prefix_len``
    are garbage and masked); prefix_len/suffix_len: scalars. Suffix token
    i sits at absolute position ``prefix_len + i`` and attends to every
    valid prefix position plus suffix positions <= i — the same causal
    math as :func:`gpt_prefill`, computed for B tokens instead of the
    whole prompt. Returns ``(logits (1, B, V), kv (L, 2, B, H*Dh))`` —
    the suffix K/V for the cache scatter (the pool's rows, like the
    prefill's).
    """
    from deeplearning4j_tpu.ops import exec_op

    emb = params["embeddings"]
    n, b = ids.shape
    t_pre = prefix_kv.shape[2]
    h, dh = cfg.heads, cfg.hidden // cfg.heads
    pos = jnp.clip(prefix_len + jnp.arange(b), 0, cfg.max_position - 1)
    x = emb["word"][ids] + emb["position"][pos][None]
    x = _layer_norm(x, emb["ln_gamma"], emb["ln_beta"], cfg.layer_norm_eps)

    def split(a):  # (1, B, E) -> (1, H, B, Dh)
        return a.reshape(n, b, h, dh).transpose(0, 2, 1, 3)

    # (1, 1, B, Tpre + B) bool: query i -> prefix j < prefix_len, then
    # suffix j' <= i (causal) and j' < suffix_len (padding)
    qi = jnp.arange(b)[:, None]
    m_pre = jnp.broadcast_to(jnp.arange(t_pre)[None, :] < prefix_len,
                             (b, t_pre))
    js = jnp.arange(b)[None, :]
    m_suf = (js <= qi) & (js < suffix_len)
    m4 = jnp.concatenate([m_pre, m_suf], axis=1)[None, None]

    def heads_first(a):  # (Tpre, H*Dh) -> (1, H, Tpre, Dh)
        return a.reshape(t_pre, h, dh).transpose(1, 0, 2)[None]

    kvs = []
    for li, blk in enumerate(params["blocks"]):
        a = blk["attn"]
        q = split(x @ a["Wq"] + a["bq"])
        k_row = x @ a["Wk"] + a["bk"]
        v_row = x @ a["Wv"] + a["bv"]
        kvs.append(jnp.stack([k_row[0], v_row[0]]))  # (2, B, H*Dh)
        k, v = split(k_row), split(v_row)
        kp = heads_first(prefix_kv[li, 0])
        vp = heads_first(prefix_kv[li, 1])
        out = exec_op("dot_product_attention", q,
                      jnp.concatenate([kp, k], axis=2),
                      jnp.concatenate([vp, v], axis=2), m4, scaled=True)
        out = out.transpose(0, 2, 1, 3).reshape(n, b, cfg.hidden)
        x = _layer_norm(x + out @ a["Wo"] + a["bo"],
                        a["ln_gamma"], a["ln_beta"], cfg.layer_norm_eps)
        x = _ffn(blk, x, cfg.layer_norm_eps)
    logits = x @ emb["word"].T
    return logits, jnp.stack(kvs)


def gpt_verify(params, kv_pages, tokens, seq_lens, page_table, write_pages,
               write_offsets, cfg: GptConfig, *, page_size: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative-decoding verification: score ``B = K + 1`` proposed
    tokens per slot in ONE causal forward against the paged KV cache
    (docs/SERVING.md § Speculative decoding).

    kv_pages: (L, 2, P, page, H*Dh) — functionally updated (donate it);
    tokens: (S, B) int32 — per slot, the last committed token followed by
    the draft's K proposals; seq_lens: (S,) tokens already CACHED for the
    slot (the fed run occupies absolute positions ``seq_lens + i``);
    page_table: (S, max_pages) int32; write_pages/write_offsets: (S, B)
    where each fed token's K/V lands (the engine points inactive slots at
    its trash page). Fed token ``i`` attends to every cached position
    ``< seq_lens`` plus fed positions ``<= i`` — the same causal math as
    :func:`gpt_prefill`, restricted to the B-token window. Returns
    ``(kv_pages, greedy (S, B) int32)`` — the target's argmax at every
    fed position, which is all greedy acceptance needs: proposal ``d_i``
    is accepted iff it equals the argmax at position ``i - 1``, and the
    argmax after the accepted prefix is the correction/bonus token.

    The K/V of EVERY fed token is scattered (positions past the accepted
    prefix become garbage beyond the engine's rewound ``seq_lens`` —
    never read, overwritten by the next pass), so acceptance costs no
    second write pass.
    """
    from deeplearning4j_tpu.ops import exec_op
    from deeplearning4j_tpu.ops.pallas_attention import gather_pages

    emb = params["embeddings"]
    s_n, b = tokens.shape
    t_v = page_table.shape[1] * page_size
    h, dh = cfg.heads, cfg.hidden // cfg.heads
    pos = jnp.clip(seq_lens[:, None] + jnp.arange(b)[None, :], 0,
                   cfg.max_position - 1)
    x = emb["word"][tokens] + emb["position"][pos]
    x = _layer_norm(x, emb["ln_gamma"], emb["ln_beta"], cfg.layer_norm_eps)

    def split(a):  # (S, B, E) -> (S, H, B, Dh)
        return a.reshape(s_n, b, h, dh).transpose(0, 2, 1, 3)

    # (S, 1, B, Tv + B) bool: query i -> cached j < seq_lens, then fed
    # j' <= i (causal within the window). Fed tokens also land in the
    # gathered page range at positions >= seq_lens, but the cached-side
    # mask excludes them — their fresh K/V enters via the concat instead.
    tpos = jnp.arange(t_v)
    m_ctx = jnp.broadcast_to((tpos[None, None, :]
                              < seq_lens[:, None, None]), (s_n, b, t_v))
    qi = jnp.arange(b)[:, None]
    m_fed = jnp.broadcast_to(jnp.arange(b)[None, :] <= qi, (b, b))
    m4 = jnp.concatenate(
        [m_ctx, jnp.broadcast_to(m_fed[None], (s_n, b, b))],
        axis=2)[:, None]

    def cached(rows):  # the slots' page runs (S, P, page, E) -> (S,H,Tv,Dh)
        return rows.reshape(s_n, t_v, h, dh).transpose(0, 2, 1, 3)

    for li, blk in enumerate(params["blocks"]):
        a = blk["attn"]
        q = split(x @ a["Wq"] + a["bq"])
        k_row = x @ a["Wk"] + a["bk"]
        v_row = x @ a["Wv"] + a["bv"]
        # scatter the fed rows; trash-page duplicates are benign
        kv_pages = kv_pages.at[li, 0, write_pages, write_offsets].set(k_row)
        kv_pages = kv_pages.at[li, 1, write_pages, write_offsets].set(v_row)
        k, v = split(k_row), split(v_row)
        kc = cached(gather_pages(kv_pages, li, 0, page_table))
        vc = cached(gather_pages(kv_pages, li, 1, page_table))
        out = exec_op("dot_product_attention", q,
                      jnp.concatenate([kc, k], axis=2),
                      jnp.concatenate([vc, v], axis=2), m4, scaled=True)
        out = out.transpose(0, 2, 1, 3).reshape(s_n, b, cfg.hidden)
        x = _layer_norm(x + out @ a["Wo"] + a["bo"],
                        a["ln_gamma"], a["ln_beta"], cfg.layer_norm_eps)
        x = _ffn(blk, x, cfg.layer_norm_eps)
    logits = x @ emb["word"].T
    return kv_pages, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def gpt_decode_step(params, kv_pages, tokens, positions, page_table,
                    seq_lens_incl, write_page, write_offset, cfg: GptConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode token for every slot, against the paged KV cache.

    kv_pages: (L, 2, P, page, H*Dh) — the whole pool, updated in place
    (donate it): this token's K/V rows are scattered into it and the
    attention reads it where it lies, the layer picked inside the op, so no
    layer of it is ever sliced out;
    tokens/positions: (S,) int32 — the token being fed and its position;
    page_table: (S, max_pages) int32; seq_lens_incl: (S,) valid length
    INCLUDING this token; write_page/write_offset: (S,) where this token's
    K/V land (the engine points inactive slots at its trash page).
    Returns ``(kv_pages, logits (S, V))``.
    """
    from deeplearning4j_tpu.ops import exec_op

    emb = params["embeddings"]
    s_n = tokens.shape[0]
    h, dh = cfg.heads, cfg.hidden // cfg.heads
    pos = jnp.clip(positions, 0, cfg.max_position - 1)
    x = emb["word"][tokens] + emb["position"][pos]
    x = _layer_norm(x, emb["ln_gamma"], emb["ln_beta"], cfg.layer_norm_eps)
    for li, blk in enumerate(params["blocks"]):
        a = blk["attn"]
        q = (x @ a["Wq"] + a["bq"]).reshape(s_n, h, dh)
        kv_pages = kv_pages.at[li, 0, write_page, write_offset].set(
            x @ a["Wk"] + a["bk"])
        kv_pages = kv_pages.at[li, 1, write_page, write_offset].set(
            x @ a["Wv"] + a["bv"])
        attn = exec_op("paged_decode_attention", q, kv_pages, page_table,
                       seq_lens_incl, layer=li, scale=1.0 / math.sqrt(dh))
        attn = attn.reshape(s_n, cfg.hidden)
        x = _layer_norm(x + attn @ a["Wo"] + a["bo"],
                        a["ln_gamma"], a["ln_beta"], cfg.layer_norm_eps)
        x = _ffn(blk, x, cfg.layer_norm_eps)
    logits = x @ emb["word"].T
    return kv_pages, logits


def gpt_cache_rows(cfg: GptConfig) -> CacheRows:
    """A key row and a value row a token a layer, heads merged."""
    if cfg.hidden % cfg.heads:
        raise ValueError("hidden must be divisible by heads")
    return CacheRows(layers=cfg.layers, sides=2, width=cfg.hidden)


def gpt_programs(cfg: GptConfig) -> ServingPrograms:
    """What the serving engine asks a model for (models/served.py): the
    functions above bound to ``cfg`` (needs no weights)."""

    def prefill(params, ids, prompt_len):
        mask = (jnp.arange(ids.shape[1]) < prompt_len)[None, :]
        logits, kv = gpt_prefill(params, ids, cfg,
                                 mask=mask.astype(jnp.int32))
        return logits[0, prompt_len - 1][None], kv[:, :, 0], None

    def decode_step(*args):
        return (*gpt_decode_step(*args, cfg), None)

    def prefill_suffix(*args):
        return gpt_prefill_suffix(*args, cfg)

    def verify(*args, page_size: int):
        return gpt_verify(*args, cfg, page_size=page_size)

    return ServingPrograms(prefill=prefill, decode_step=decode_step,
                           prefill_suffix=prefill_suffix, verify=verify)


def reference_generate(params, cfg: GptConfig, prompt, n_new: int
                       ) -> np.ndarray:
    """Greedy autoregressive oracle: re-runs the FULL causal prefill for
    every generated token — O(T²) per token, test-sized only. The paged
    decode path must reproduce these tokens exactly (tests/test_serving.py
    greedy-equivalence gate)."""
    toks = list(np.asarray(prompt).tolist())
    for _ in range(n_new):
        ids = jnp.asarray(np.array(toks, np.int32)[None])
        logits, _ = gpt_prefill(params, ids, cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return np.array(toks[len(prompt):], np.int32)


class GptModel:
    """Decoder model handle: config + params (+ serde). The serving loop
    (``serving.GenerativeEngine``) owns batching, cache, and sampling."""

    def __init__(self, cfg: GptConfig, seed: int = 0, dtype=jnp.float32,
                 params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.params = params if params is not None else init_gpt_params(
            jax.random.key(seed), cfg, dtype)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.params))

    def cache_rows(self) -> CacheRows:
        return gpt_cache_rows(self.cfg)

    def serving_programs(self) -> ServingPrograms:
        return gpt_programs(self.cfg)

    def logits(self, ids) -> np.ndarray:
        """Convenience full-sequence forward (no cache)."""
        out, _ = gpt_prefill(self.params, jnp.asarray(ids, jnp.int32),
                             self.cfg)
        return np.asarray(out)


# ---------------------------------------------------------------------------
# serde — the ModelSerializer zip layout (nn/serde.py) for the raw pytree
# ---------------------------------------------------------------------------


def save_gpt(model: GptModel, path: str) -> None:
    """configuration.json + coefficients.bin, the nn/serde.py zip layout.
    The coefficients buffer is f32 (widening bf16 losslessly); meta.json
    records the param dtype so restore casts back instead of silently
    promoting a bf16 model to f32 (2x param + KV-cache memory)."""
    from deeplearning4j_tpu.nn.serde import flatten_pytree

    dtype = str(jnp.dtype(jax.tree.leaves(model.params)[0].dtype))
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("configuration.json", model.cfg.to_json())
        z.writestr("meta.json", json.dumps({"dtype": dtype}))
        z.writestr("coefficients.bin", flatten_pytree(model.params).tobytes())


def restore_gpt(path: str) -> GptModel:
    from deeplearning4j_tpu.nn.serde import unflatten_pytree

    with zipfile.ZipFile(path, "r") as z:
        cfg = GptConfig.from_json(z.read("configuration.json").decode())
        flat = np.frombuffer(z.read("coefficients.bin"), np.float32)
        dtype = jnp.float32
        if "meta.json" in z.namelist():
            dtype = jnp.dtype(json.loads(z.read("meta.json"))["dtype"])
    # abstract template: same structure/shapes/dtypes, zero materialization
    # cost (a real init would burn the full param memory + PRNG time just
    # to be overwritten)
    template = jax.eval_shape(
        lambda: init_gpt_params(jax.random.key(0), cfg, dtype))
    return GptModel(cfg, params=unflatten_pytree(template, flat))
