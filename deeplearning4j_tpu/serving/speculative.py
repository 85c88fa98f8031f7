"""Speculative decoding — draft-model propose, single-pass target verify.

ROADMAP item 2(b): decode is the memory-bound hot path of the serving
tier — every generated token costs one full target forward whose time is
dominated by weight/KV traffic, not FLOPs. Draft-then-verify (Leviathan
et al. 2023, Chen et al. 2023) buys tokens-per-forward without changing
the output distribution: a cheap DRAFT model proposes ``K`` greedy
continuations, then ONE batched target forward (``models.gpt.gpt_verify``
— the PR-12 suffix-prefill shape, K+1 tokens against the paged cache)
scores all proposals at once. Greedy exact-match acceptance commits the
agreed prefix plus the target's own next token at the first disagreement
(the correction) or after a full accept (the bonus) — so every verify
commits between 1 and K+1 tokens and the committed stream is
**bit-identical** to non-speculative greedy decoding, by construction —
scoped to the verify and decode attention paths agreeing on argmax:
``gpt_verify`` runs the registry's dense attention while plain decode
runs the paged kernel, so on-device a near-tie logit could in principle
resolve differently between them (docs/SERVING.md § Speculative
decoding, "On-device caveat"; the CPU gates share one implementation,
and ``tests/test_serving.py`` asserts Pallas-vs-XLA greedy agreement at
test scale).

This module owns the DRAFT half:

* a **dense per-slot draft KV cache** ``(L, 2, max_slots, max_ctx + 1,
  H * Dh)`` — rows as the paged pool holds them (heads merged), but the
  draft is small, so the paged indirection would cost more than it saves;
  the final position is the trash position (inactive slots' writes land
  there, mirroring the page trick);
* ``draft_prefill`` — the draft's full-prompt pass at admission (the
  prompt rides the same ``max_prompt`` bucket as the target prefill);
* ``draft_decode`` — ONE compiled fn proposing all K tokens: a
  ``lax.scan`` of K greedy decode steps over the whole slot bank.

Both signatures depend only on server-start configuration
``(max_slots, max_prompt, max_ctx, spec_k)`` — the RecompileLedger shows
exactly one ``first_compile`` each (keys ``draft_prefill`` /
``draft_decode``) and ZERO ``new_shape`` across admits/evicts/rejections/
restarts (gate-asserted, like the four target functions).

**Rollback** is O(1) host bookkeeping: the verify pass writes K/V for
every fed token, and a rejection simply REWINDS the committed length —
target-side ``cache.seq_lens`` and draft-side :attr:`lens` — leaving the
rejected positions as garbage beyond the length that attention (which
masks ``>= seq_len``) never reads and the next pass overwrites. No pages
are freed on rollback (refcount-safe: shared prefix-cache pages are
never written past the prompt, so a rewind cannot corrupt the radix
tree — tests/test_speculative.py exercises page-boundary rollbacks on
shared pages).

**Supervision**: a crash recovery reallocates the (possibly mid-donation)
draft KV buffer with :meth:`reset` — same shape, so the compiled draft
fns survive and retried requests re-prefill from the prompt, staying
lossless.

Metrics: ``dl4j_tpu_spec_{proposed,accepted,rejected}_tokens_total``
counters plus the ``dl4j_tpu_spec_accept_ratio`` histogram (per-verify
accepted/K — the acceptance-rate signal); ``serving_draft`` /
``serving_verify`` spans come from the engine (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.models.bert import _layer_norm
from deeplearning4j_tpu.models.gpt import GptModel, _ffn, gpt_prefill

#: acceptance-ratio histogram bounds — fractions of K, not latencies
_ACCEPT_BOUNDS = tuple(i / 10.0 for i in range(11))


def perturbed_draft(model: GptModel, *, scale: float = 1e-2,
                    seed: int = 0) -> GptModel:
    """A deterministic distillation STAND-IN for harnesses: the target's
    own params plus small seeded Gaussian noise, same config. Greedy
    agreement with the target is high but not total, so replay/gate legs
    exercise accepts AND rejections reproducibly — a real deployment
    pairs a trained GPT-tiny draft (``models.GPT(...).init_draft()``)
    instead."""
    leaves, treedef = jax.tree.flatten(model.params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    noisy = [l + jnp.asarray(scale, l.dtype)
             * jax.random.normal(k, l.shape, l.dtype)
             for l, k in zip(leaves, keys)]
    return GptModel(model.cfg, params=jax.tree.unflatten(treedef, noisy))


def _draft_decode_step(params, kv, tokens, pos, active, cfg):
    """One greedy draft token for every slot against the dense cache.

    kv: (L, 2, S, T+1, H*Dh) — position T is the trash position;
    tokens/pos: (S,) the fed token and its absolute position; active:
    (S,) int32. Writes the fed token's K/V at ``pos`` (trash when
    inactive), attends over positions ``<= pos``, returns
    ``(kv, logits (S, V))``.
    """
    from deeplearning4j_tpu.ops import exec_op

    emb = params["embeddings"]
    s_n = tokens.shape[0]
    t_all = kv.shape[3]
    h, dh = cfg.heads, cfg.hidden // cfg.heads
    p = jnp.clip(pos, 0, cfg.max_position - 1)
    x = emb["word"][tokens] + emb["position"][p]
    x = _layer_norm(x, emb["ln_gamma"], emb["ln_beta"], cfg.layer_norm_eps)
    wpos = jnp.where(active > 0, pos, t_all - 1)
    rows = jnp.arange(s_n)
    # (S, 1, 1, T): key j is readable once written — j <= pos (history
    # plus the token this very step writes); the trash position never
    # enters the mask
    m4 = (jnp.arange(t_all - 1)[None, :] <= pos[:, None])[:, None, None, :]
    for li, blk in enumerate(params["blocks"]):
        a = blk["attn"]
        q = (x @ a["Wq"] + a["bq"]).reshape(s_n, h, 1, dh)
        kv = kv.at[li, 0, rows, wpos].set(x @ a["Wk"] + a["bk"])
        kv = kv.at[li, 1, rows, wpos].set(x @ a["Wv"] + a["bv"])
        kc, vc = (kv[li, c, :, :t_all - 1].reshape(
            s_n, t_all - 1, h, dh).transpose(0, 2, 1, 3)  # (S, H, T, Dh)
            for c in (0, 1))
        out = exec_op("dot_product_attention", q, kc, vc, m4, scaled=True)
        out = out.reshape(s_n, cfg.hidden)
        x = _layer_norm(x + out @ a["Wo"] + a["bo"],
                        a["ln_gamma"], a["ln_beta"], cfg.layer_norm_eps)
        x = _ffn(blk, x, cfg.layer_norm_eps)
    return kv, x @ emb["word"].T


class SpeculativeDecoder:
    """The draft half of speculative decoding: dense per-slot draft KV,
    the two compiled draft functions, and the commit/rollback/reset
    bookkeeping the engine drives (module docstring has the design).

    Invariant (``GenerativeEngine.check_invariants`` asserts it): for
    every speculating slot, :attr:`lens` equals the target cache's
    ``seq_lens`` — draft and target always agree on how many tokens are
    committed-and-cached; for every other slot it is zero.
    """

    def __init__(self, draft_model: GptModel, *, k: int, max_slots: int,
                 max_ctx: int, max_prompt: int):
        if k <= 0:
            raise ValueError(f"spec_k must be positive, got {k}")
        self.draft = draft_model
        cfg = draft_model.cfg
        self.k = int(k)
        self.max_slots = int(max_slots)
        self.max_ctx = int(max_ctx)
        self.max_prompt = int(max_prompt)
        if cfg.max_position < self.max_prompt:
            raise ValueError(
                f"draft max_position={cfg.max_position} cannot prefill the "
                f"engine's max_prompt={max_prompt} bucket")
        dtype = jax.tree.leaves(draft_model.params)[0].dtype
        # +1: the trash position — inactive slots' scan writes land there
        self._kv_shape = (cfg.layers, 2, self.max_slots, self.max_ctx + 1,
                          cfg.hidden)
        self._kv_dtype = dtype
        self.kv = jnp.zeros(self._kv_shape, dtype)
        self.lens = np.zeros((self.max_slots,), np.int32)
        self._prefill_fn = None
        self._propose_fn = None
        m = observe.metrics()
        self._c_proposed = m.counter("dl4j_tpu_spec_proposed_tokens_total")
        self._c_accepted = m.counter("dl4j_tpu_spec_accepted_tokens_total")
        self._c_rejected = m.counter("dl4j_tpu_spec_rejected_tokens_total")
        self._h_ratio = m.histogram("dl4j_tpu_spec_accept_ratio",
                                    bounds=_ACCEPT_BOUNDS)

    # ---------------------------------------------------------- compiled fns
    def _build_prefill(self):
        cfg = self.draft.cfg

        @functools.partial(jax.jit, donate_argnums=(1,))
        def draft_prefill(params, kv, ids, prompt_len, slot):
            mask = (jnp.arange(ids.shape[1]) < prompt_len)[None, :]
            _logits, kvp = gpt_prefill(params, ids, cfg,
                                       mask=mask.astype(jnp.int32))
            # kvp (L, 2, 1, Tpre, H*Dh) drops into the slot's row;
            # positions >= prompt_len hold pad garbage the <= pos decode
            # mask never reads (the first propose overwrites position
            # prompt_len before attending to it)
            return jax.lax.dynamic_update_slice(
                kv, kvp, (0, 0, slot, 0, 0))

        return draft_prefill

    def _build_propose(self):
        cfg, k = self.draft.cfg, self.k

        @functools.partial(jax.jit, donate_argnums=(1,))
        def draft_decode(params, kv, tokens, lens, active):
            def body(carry, _):
                kv, toks, pos = carry
                kv, logits = _draft_decode_step(params, kv, toks, pos,
                                                active, cfg)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (kv, nxt, pos + (active > 0).astype(jnp.int32)), nxt

            # k + 1 steps for k proposals: the LAST iteration exists only
            # to write d_K's K/V (feeding it, discarding its output) — a
            # full accept commits K+1 tokens and :meth:`commit` advances
            # the draft length over position lens+K, so that position
            # must hold real K/V or every later draft step for the slot
            # would attend to a garbage hole INSIDE the claimed length,
            # silently decaying acceptance for the rest of the sequence
            (kv, _, _), props = jax.lax.scan(body, (kv, tokens, lens),
                                             None, length=k + 1)
            return kv, jnp.transpose(props)[:, :k]  # (S, K)

        return draft_decode

    # ------------------------------------------------------------- lifecycle
    def prefill(self, slot: int, prompt) -> None:
        """Run the draft over ``slot``'s (bucket-padded) prompt at
        admission; afterwards the draft agrees with the target on a
        cached length of ``prompt_len``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p_len = int(prompt.size)
        ids = np.zeros((1, self.max_prompt), np.int32)
        ids[0, :p_len] = prompt
        if self._prefill_fn is None:
            self._prefill_fn = self._build_prefill()
        observe.note_jit_signature(
            self._prefill_fn, graph="serving", key="draft_prefill",
            signature=observe.signature_of(ids=ids))
        with observe.tracer().span("serving_draft", category="serving",
                                   phase="prefill", prompt_len=p_len):
            self.kv = self._prefill_fn(
                self.draft.params, self.kv, jnp.asarray(ids),
                jnp.asarray(p_len, jnp.int32), jnp.asarray(slot, jnp.int32))
        self.lens[slot] = p_len

    def propose(self, tokens: np.ndarray, active: np.ndarray) -> np.ndarray:
        """Propose K greedy draft tokens for every active slot, feeding
        each slot's pending token first. Advances the draft KV (rejected
        tails are rewound by :meth:`commit`); returns (S, K) int32."""
        if self._propose_fn is None:
            self._propose_fn = self._build_propose()
        observe.note_jit_signature(
            self._propose_fn, graph="serving", key="draft_decode",
            signature=observe.signature_of(tokens=tokens, lens=self.lens,
                                           active=active))
        with observe.tracer().span("serving_draft", category="serving",
                                   phase="decode",
                                   slots=int(active.sum())):
            self.kv, props = self._propose_fn(
                self.draft.params, self.kv, jnp.asarray(tokens),
                jnp.asarray(self.lens), jnp.asarray(active))
            return np.asarray(props)

    def commit(self, slot: int, n_tokens: int) -> None:
        """Advance ``slot``'s draft length by the tokens the verify pass
        actually committed — everything past it is the rollback: garbage
        beyond the length, overwritten by the next propose."""
        self.lens[slot] += int(n_tokens)

    def note_outcome(self, proposed: int, accepted: int,
                     committed_from_draft: int) -> None:
        """Count one slot's verify outcome. The counters are ADDITIVE by
        construction — ``proposed == accepted + rejected`` always:
        ``accepted`` counts draft tokens that actually COMMITTED,
        ``rejected`` everything proposed that did not land (target
        disagreement OR eos/budget truncation). The pure
        disagreement-rate signal (verified agreement ``accepted``/K,
        truncation excluded) is the ``accept_ratio`` histogram."""
        self._c_proposed.inc(proposed)
        self._c_accepted.inc(committed_from_draft)
        self._c_rejected.inc(proposed - committed_from_draft)
        if proposed:
            self._h_ratio.observe(accepted / proposed)

    def free(self, slot: int) -> None:
        """Retire ``slot``'s draft row (length 0; the KV bytes are
        garbage-beyond-length until the next tenant's prefill)."""
        self.lens[slot] = 0

    def reset(self) -> None:
        """Supervised crash recovery: reallocate the (possibly
        mid-donation) draft KV buffer — same shape, so the compiled draft
        fns survive and the ledger's zero-new_shape property holds across
        restarts — and zero every draft length (retried requests
        re-prefill from the prompt)."""
        self.kv = jnp.zeros(self._kv_shape, self._kv_dtype)
        self.lens[:] = 0

    # ------------------------------------------------------------ inspection
    def check_invariants(self, active_spec_slots, seq_lens) -> None:
        """Draft/target length agreement (test/chaos hook): every
        speculating slot's draft length equals the target cache's, every
        other slot's is zero. Raises AssertionError on violation."""
        for slot in range(self.max_slots):
            if slot in active_spec_slots:
                assert self.lens[slot] == seq_lens[slot], (
                    f"slot {slot}: draft cached {self.lens[slot]} tokens "
                    f"but the target cache holds {seq_lens[slot]}")
            else:
                assert self.lens[slot] == 0, (
                    f"slot {slot} is not speculating but holds a draft "
                    f"length of {self.lens[slot]}")


__all__: List[str] = ["SpeculativeDecoder", "perturbed_draft"]
