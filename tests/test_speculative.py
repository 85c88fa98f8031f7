"""Speculative decoding tests (docs/SERVING.md § Speculative decoding).

The one property everything else hangs off: speculation is LOSSLESS —
greedy engine output with a draft model (ANY draft model, however wrong)
is token-for-token identical to ``reference_generate``'s full-attention
oracle and to the spec-off engine. Covered across:

  * accept-all (draft == target) and reject-at-every-position (a
    zeroed draft proposing a constant token the target never emits);
  * mid-flight admits/evicts with more requests than slots, mixed with
    sampling (temperature > 0) slots that must fall back to the plain
    decode path;
  * page-boundary rollbacks on SHARED (prefix-cache-mapped) pages — a
    rejection rewind must never corrupt a page the radix tree still
    serves;
  * supervisor restarts mid-speculation (``decode_step_error`` inside
    the verify step): retries re-prefill and stay lossless, the draft KV
    drops with the restart, zero ``new_shape`` ledger events;
  * the compile-once contract: exactly one ``first_compile`` for each of
    draft_prefill / draft_decode / verify, zero ``new_shape`` across
    admits/evicts/rejections/restarts;
  * per-committed-token inter-token accounting (a 4-token step reads as
    4 gaps of step/4, keeping spec-on percentiles comparable);
  * the frontend's ``ClassPolicy.disable_spec`` degraded-mode knob and
    the zoo's draft/target config pairing.
"""

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import faults, models, observe
from deeplearning4j_tpu.models.gpt import (
    GptConfig, GptModel, draft_config_for, reference_generate,
)
from deeplearning4j_tpu.serving import (
    ClassPolicy, GenerativeEngine, SLOFrontend, default_classes,
    perturbed_draft,
)

CFG = GptConfig.tiny()
MODEL = GptModel(CFG, seed=1)
#: all-zero params: LN(0) = 0 through every block, logits = 0, argmax =
#: token 0 — a draft that CONSTANTLY proposes token 0, for deterministic
#: reject-at-every-position runs (prompts/targets below avoid token 0)
ZDRAFT = GptModel(CFG, params=jax.tree.map(lambda a: a * 0.0, MODEL.params))

PROMPTS = [np.array([3, 5, 7, 9], np.int32),
           np.array([11, 2], np.int32),
           np.array([42, 43, 44, 45, 46, 47], np.int32),
           np.array([8, 8, 8], np.int32),
           np.array([17, 23, 31], np.int32)]


def make_engine(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 6)
    kw.setdefault("max_prompt", 16)
    kw.setdefault("seed", 3)
    return GenerativeEngine(MODEL, **kw)


def oracle(prompt, n):
    return reference_generate(MODEL.params, CFG, prompt, n).tolist()


def serving_new_shape():
    return sum(1 for e in observe.ledger().events()
               if e.graph == "serving" and e.cause == "new_shape")


# ---------------------------------------------------------------------------
# draft half — the dense-cache propose path
# ---------------------------------------------------------------------------


class TestDraftDecoder:
    def test_propose_matches_draft_oracle(self):
        """The dense-cache draft loop IS greedy decoding of the draft
        model: proposals after a prefilled prompt must equal the draft's
        own full-attention greedy continuation."""
        from deeplearning4j_tpu.serving.speculative import SpeculativeDecoder

        spec = SpeculativeDecoder(MODEL, k=4, max_slots=2, max_ctx=48,
                                  max_prompt=16)
        prompt = PROMPTS[0]
        spec.prefill(0, prompt)
        want = reference_generate(MODEL.params, CFG, prompt, 5)
        # feed the draft's own first greedy token, as the engine feeds
        # the target's (identical here: same model)
        pend = np.zeros((2,), np.int32)
        pend[0] = want[0]
        props = spec.propose(pend, np.array([1, 0], np.int32))
        assert props[0].tolist() == want[1:].tolist()
        # the inactive slot's row was never touched
        assert spec.lens[1] == 0

    def test_commit_rewind_and_reset(self):
        from deeplearning4j_tpu.serving.speculative import SpeculativeDecoder

        spec = SpeculativeDecoder(MODEL, k=2, max_slots=1, max_ctx=32,
                                  max_prompt=16)
        spec.prefill(0, PROMPTS[0])
        assert spec.lens[0] == 4
        spec.commit(0, 3)
        assert spec.lens[0] == 7
        spec.free(0)
        assert spec.lens[0] == 0
        spec.prefill(0, PROMPTS[1])
        spec.reset()
        assert spec.lens[0] == 0

    def test_validation(self):
        from deeplearning4j_tpu.serving.speculative import SpeculativeDecoder

        with pytest.raises(ValueError, match="spec_k"):
            SpeculativeDecoder(MODEL, k=0, max_slots=1, max_ctx=32,
                               max_prompt=16)
        small = GptModel(GptConfig.tiny(max_position=8), seed=0)
        with pytest.raises(ValueError, match="max_position"):
            SpeculativeDecoder(small, k=2, max_slots=1, max_ctx=32,
                               max_prompt=16)

    def test_engine_requires_matching_draft(self):
        with pytest.raises(ValueError, match="draft_model"):
            make_engine(spec_k=2)
        bad = GptModel(GptConfig.tiny(vocab_size=128), seed=0)
        with pytest.raises(ValueError, match="vocab"):
            make_engine(spec_k=2, draft_model=bad)


# ---------------------------------------------------------------------------
# losslessness — the whole point
# ---------------------------------------------------------------------------


class TestLossless:
    def test_accept_all_matches_oracle(self):
        """draft == target: every proposal accepted, outputs still exact
        (the bonus-token arithmetic and budget truncation must not leak
        an extra or missing token)."""
        eng = make_engine(spec_k=4, draft_model=MODEL)
        res = eng.generate(PROMPTS, max_new_tokens=12, eos_token=-1)
        for r, p in zip(res, PROMPTS):
            assert r.tokens.tolist() == oracle(p, 12)
            assert r.spec_proposed_tokens > 0
            assert r.spec_accepted_tokens > 0
        eng.check_invariants()

    def test_reject_at_every_position_matches_oracle(self):
        """The zeroed draft proposes token 0 forever; target trajectories
        here never emit 0, so EVERY verify rejects at position 0 and
        commits exactly one correction token — the degenerate case that
        must equal plain decoding step-for-step."""
        for p in PROMPTS:
            assert 0 not in oracle(p, 10)  # precondition for determinism
        eng = make_engine(spec_k=3, draft_model=ZDRAFT)
        res = eng.generate(PROMPTS, max_new_tokens=10, eos_token=-1)
        for r, p in zip(res, PROMPTS):
            assert r.tokens.tolist() == oracle(p, 10)
            assert r.spec_accepted_tokens == 0
            assert r.spec_proposed_tokens > 0
        eng.check_invariants()

    def test_partial_acceptance_matches_oracle(self):
        """A perturbed draft agrees often but not always — accepts,
        rejections, and corrections all interleave and the stream stays
        exact (the replay/gate measurement model)."""
        draft = perturbed_draft(MODEL, scale=2e-3, seed=5)
        eng = make_engine(spec_k=4, draft_model=draft)
        res = eng.generate(PROMPTS, max_new_tokens=14, eos_token=-1)
        for r, p in zip(res, PROMPTS):
            assert r.tokens.tolist() == oracle(p, 14)
        eng.check_invariants()

    def test_eos_inside_committed_window(self):
        """An eos landing mid-window must cut the commit exactly there —
        same tokens and finish reason as the spec-off engine."""
        p = PROMPTS[0]
        eos_tok = oracle(p, 8)[3]
        for draft in (MODEL, ZDRAFT):
            on = make_engine(spec_k=4, draft_model=draft).generate(
                [p], max_new_tokens=8, eos_token=eos_tok)[0]
            off = make_engine().generate(
                [p], max_new_tokens=8, eos_token=eos_tok)[0]
            assert on.finish_reason == off.finish_reason == "eos"
            assert on.tokens.tolist() == off.tokens.tolist()

    def test_max_new_tokens_budget_never_overshoots(self):
        """Multi-token commits must truncate at the budget, including
        budgets smaller than the verify window."""
        p = PROMPTS[2]
        for budget in (1, 2, 5):
            r = make_engine(spec_k=4, draft_model=MODEL).generate(
                [p], max_new_tokens=budget, eos_token=-1)[0]
            assert r.tokens.tolist() == oracle(p, budget)
            assert r.finish_reason == "length"


# ---------------------------------------------------------------------------
# scheduler integration — admits/evicts, sampling fallback, accounting
# ---------------------------------------------------------------------------


class TestSchedulerIntegration:
    def test_midflight_admits_and_evicts(self):
        """More requests than slots: retire/admit churn between verify
        windows, every greedy output exact, zero new_shape."""
        before = serving_new_shape()
        eng = make_engine(spec_k=3, draft_model=perturbed_draft(
            MODEL, scale=2e-3, seed=9), max_slots=2)
        lens = [5, 11, 3, 8, 14]
        futs = []
        eng.start()
        try:
            for p, n in zip(PROMPTS, lens):
                futs.append(eng.submit(p, max_new_tokens=n, eos_token=-1))
            res = [f.result(timeout=120) for f in futs]
        finally:
            eng.stop()
        for r, p, n in zip(res, PROMPTS, lens):
            assert r.finish_reason == "length"
            assert r.tokens.tolist() == oracle(p, n)
        assert serving_new_shape() == before
        eng.check_invariants()

    def test_sampling_slots_fall_back_to_plain_decode(self):
        """temperature > 0 slots never speculate — they ride the plain
        decode dispatch next to speculating greedy neighbours."""
        eng = make_engine(spec_k=3, draft_model=MODEL)
        eng.start()
        try:
            f_greedy = eng.submit(PROMPTS[0], max_new_tokens=8,
                                  eos_token=-1)
            f_sample = eng.submit(PROMPTS[1], max_new_tokens=8,
                                  temperature=0.9, top_k=12, eos_token=-1)
            rg, rs = f_greedy.result(120), f_sample.result(120)
        finally:
            eng.stop()
        assert rg.tokens.tolist() == oracle(PROMPTS[0], 8)
        assert rg.spec_proposed_tokens > 0
        assert rs.spec_proposed_tokens == 0 and len(rs.tokens) == 8
        eng.check_invariants()

    @pytest.mark.parametrize("hidden,mode", [(64, "auto"), (128, "pallas")],
                             ids=["narrow-generic", "wide-kernel"])
    def test_near_context_limit_degrades_to_plain(self, hidden, mode):
        """A sequence whose verify window no longer fits its page-table
        row finishes NON-speculatively instead of overflowing — and the
        tokens stay exact across the switchover: the plain decode (the
        paged kernel, interpreted, at the width it takes; the gather path
        at the width it refuses) reads the rows verify scattered."""
        from deeplearning4j_tpu.environment import environment

        model = GptModel(GptConfig.tiny(hidden=hidden), seed=1)
        env = environment()
        old, env.helper_mode = env.helper_mode, mode
        try:
            # context = 2 pages * 8 = 16; prompt 6 + 10 tokens hits the edge
            eng = GenerativeEngine(model, spec_k=4, draft_model=model,
                                   max_slots=2, page_size=8, seed=3,
                                   max_pages_per_seq=2, max_prompt=8)
            p = PROMPTS[2]
            r = eng.generate([p], max_new_tokens=9, eos_token=-1)[0]
        finally:
            env.helper_mode = old
        assert r.tokens.tolist() == reference_generate(
            model.params, model.cfg, p, 9).tolist()
        assert r.finish_reason == "length"
        eng.check_invariants()

    def test_intertoken_accounting_per_committed_token(self):
        """Multi-token steps record one inter-token gap per COMMITTED
        token (step/m), so a T-token result always carries T-1 gaps and
        the histograms stay comparable to spec-off."""
        m = observe.metrics()
        itl = m.histogram("dl4j_tpu_serving_intertoken_seconds")
        dec = m.histogram("dl4j_tpu_serving_decode_step_seconds")
        itl_before, dec_before = itl.count, dec.count
        eng = make_engine(spec_k=4, draft_model=MODEL)
        res = eng.generate([PROMPTS[0]], max_new_tokens=12, eos_token=-1)[0]
        assert len(res.tokens) == 12
        assert len(res.intertoken_s) == 11
        # 11 decode-committed tokens -> 11 observations in BOTH
        # histograms (the first token is prefill, not decode)
        assert itl.count - itl_before == 11
        assert dec.count - dec_before == 11
        # accept-all with k=4: steps commit up to 5 tokens, so the
        # per-token gaps inside one step are equal by construction
        assert res.spec_accepted_tokens > 0

    def test_spec_off_by_default(self):
        eng = make_engine()
        assert eng.spec is None
        r = eng.generate([PROMPTS[0]], max_new_tokens=6, eos_token=-1)[0]
        assert r.spec_proposed_tokens == 0
        assert r.tokens.tolist() == oracle(PROMPTS[0], 6)


# ---------------------------------------------------------------------------
# rollback vs the radix prefix cache — shared pages stay sound
# ---------------------------------------------------------------------------


class TestRollbackOnSharedPages:
    def test_page_boundary_rollback_on_shared_pages(self):
        """Prefix-hit admissions map SHARED pages into the slot; the
        verify writes (and rollback rewinds) past the prompt must never
        touch them. The donor prompt must keep serving exact hits after
        a neighbour's rejection-heavy speculative run."""
        before = serving_new_shape()
        eng = make_engine(spec_k=3, draft_model=ZDRAFT, prefix_pages=8,
                          suffix_bucket=8)
        sysp = np.array([42, 43, 44, 45, 46, 47, 48, 49, 50, 51, 52],
                        np.int32)  # 11 tokens: one full page + mid-page tail
        hits = []
        for tail in ([7], [9], [7], [11]):
            p = np.concatenate([sysp, np.array(tail, np.int32)])
            r = eng.generate([p], max_new_tokens=12, eos_token=-1)[0]
            assert r.tokens.tolist() == oracle(p, 12)
            assert r.spec_accepted_tokens == 0  # every position rejected
            hits.append(r.prefix_hit_tokens)
        assert hits[0] == 0 and all(h > 0 for h in hits[1:])
        assert serving_new_shape() == before
        eng.check_invariants()  # exact refcounts + draft/target lengths

    def test_concurrent_shared_prefix_spec_slots(self):
        """Two slots speculating over the SAME mapped prefix pages at
        once: rollbacks in both must not corrupt each other or the
        tree."""
        eng = make_engine(spec_k=3,
                          draft_model=perturbed_draft(MODEL, scale=2e-3,
                                                      seed=3),
                          prefix_pages=8, suffix_bucket=8)
        sysp = np.array([42, 43, 44, 45, 46, 47, 48, 49], np.int32)
        warm = np.concatenate([sysp, np.array([3], np.int32)])
        eng.generate([warm], max_new_tokens=2, eos_token=-1)
        p1 = np.concatenate([sysp, np.array([7], np.int32)])
        p2 = np.concatenate([sysp, np.array([9, 5], np.int32)])
        eng.start()
        try:
            f1 = eng.submit(p1, max_new_tokens=10, eos_token=-1)
            f2 = eng.submit(p2, max_new_tokens=10, eos_token=-1)
            r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
        finally:
            eng.stop()
        assert r1.tokens.tolist() == oracle(p1, 10)
        assert r2.tokens.tolist() == oracle(p2, 10)
        eng.check_invariants()


# ---------------------------------------------------------------------------
# supervision — crashes inside the verify step
# ---------------------------------------------------------------------------


class TestSupervisedSpeculation:
    def test_restart_mid_speculation_stays_lossless(self):
        """A decode_step_error fired inside the speculative step kills
        the worker mid-verify; the supervisor drops the draft KV, retries
        from the prompt, and the final stream is still oracle-exact with
        zero new_shape."""
        before = serving_new_shape()
        eng = make_engine(spec_k=3, draft_model=MODEL,
                          max_restarts=4, restart_backoff_s=0.01)
        eng.generate([PROMPTS[1]], max_new_tokens=2, eos_token=-1)  # warm
        faults.arm("decode_step_error", prob=1.0, after_n=1, max_fires=1)
        try:
            eng.start()
            fut = eng.submit(PROMPTS[0], max_new_tokens=10, eos_token=-1,
                             max_retries=2)
            res = fut.result(timeout=120)
        finally:
            eng.stop()
            faults.reset()
        assert eng.restarts == 1
        assert res.finish_reason == "length"
        assert res.tokens.tolist() == oracle(PROMPTS[0], 10)
        assert serving_new_shape() == before
        eng.check_invariants()

    def test_chaos_leg_all_terminal_invariants_hold(self):
        """The chaos contract under probabilistic verify crashes: every
        request terminal, restarts within cap, allocator + draft/target
        invariants intact, zero new_shape."""
        before = serving_new_shape()
        eng = make_engine(spec_k=3,
                          draft_model=perturbed_draft(MODEL, scale=2e-3,
                                                      seed=2),
                          max_restarts=8, restart_backoff_s=0.01)
        eng.generate([PROMPTS[1]], max_new_tokens=2, eos_token=-1)  # warm
        faults.arm("decode_step_error", prob=0.5, seed=4, max_fires=5)
        try:
            eng.start()
            futs = [eng.submit(p, max_new_tokens=8, eos_token=-1,
                               max_retries=6) for p in PROMPTS]
            res = [f.result(timeout=300) for f in futs]
        finally:
            eng.stop()
            faults.reset()
        assert all(f.done() for f in futs)
        for r, p in zip(res, PROMPTS):
            if r.finish_reason in ("eos", "length"):
                assert r.tokens.tolist() == oracle(p, 8)
        assert eng.restarts <= 8
        assert serving_new_shape() == before
        eng.check_invariants()


# ---------------------------------------------------------------------------
# frontend knob, zoo pairing
# ---------------------------------------------------------------------------


class TestDisableSpecKnob:
    def _frontend(self, eng, **kw):
        classes = default_classes()
        classes["batch"] = ClassPolicy("batch", priority=2,
                                       disable_spec=True,
                                       reject_in_shedding=False)
        return SLOFrontend(eng, classes=classes, **kw)

    def test_shedding_disables_spec_for_marked_class(self):
        eng = make_engine(spec_k=3, draft_model=MODEL).start()
        try:
            fe = self._frontend(eng)
            # force the ladder into shedding (the frontend tests' idiom)
            fe._signals = lambda: (10 ** 6, None)
            fut = fe.submit(PROMPTS[0], slo_class="batch",
                            max_new_tokens=6, eos_token=-1)
            res = fut.result(timeout=120)
        finally:
            eng.stop()
        assert fe.state == "shedding"
        assert res.spec_disabled
        assert res.spec_proposed_tokens == 0       # decoded plain
        assert res.tokens.tolist() == oracle(PROMPTS[0], 6)

    def test_ok_state_keeps_speculating(self):
        eng = make_engine(spec_k=3, draft_model=MODEL).start()
        try:
            fe = self._frontend(eng)
            fut = fe.submit(PROMPTS[0], slo_class="batch",
                            max_new_tokens=6, eos_token=-1)
            res = fut.result(timeout=120)
        finally:
            eng.stop()
        assert not res.spec_disabled
        assert res.spec_proposed_tokens > 0


class TestZooPairing:
    def test_draft_config_shares_token_space(self):
        cfg = GptConfig.base()
        d = draft_config_for(cfg)
        assert d.vocab_size == cfg.vocab_size
        assert d.eos_token == cfg.eos_token
        assert d.max_position == cfg.max_position
        assert d.hidden < cfg.hidden and d.layers < cfg.layers
        assert draft_config_for(cfg, layers=1).layers == 1

    def test_zoo_init_draft_serves(self):
        zm = models.GPT("tiny", vocab_size=256)
        target = zm.init()
        draft = zm.init_draft(layers=1)
        eng = GenerativeEngine(target, max_slots=1, page_size=8,
                               max_pages_per_seq=4, max_prompt=8,
                               spec_k=2, draft_model=draft)
        p = np.array([4, 6], np.int32)
        r = eng.generate([p], max_new_tokens=5, eos_token=-1)[0]
        want = reference_generate(target.params, target.cfg, p, 5)
        assert r.tokens.tolist() == want.tolist()


# ---------------------------------------------------------------------------
# compile-once — the ledger contract
# ---------------------------------------------------------------------------


class TestSpecJitStability:
    def test_one_compile_per_fn_zero_new_shape(self):
        led = observe.ledger()
        before = len(led.events())
        eng = make_engine(spec_k=3, draft_model=perturbed_draft(
            MODEL, scale=2e-3, seed=8))
        for n in (3, 9, 5):  # varied budgets, admits, evicts
            eng.generate([p for p in PROMPTS[:3]], max_new_tokens=n,
                         eos_token=-1)
        evs = [e for e in led.events()[before:] if e.graph == "serving"]
        by_key = {}
        for e in evs:
            by_key.setdefault(e.key, []).append(e.cause)
        assert by_key["draft_prefill"] == ["first_compile"]
        assert by_key["draft_decode"] == ["first_compile"]
        assert by_key["verify"] == ["first_compile"]
        assert all(c == "first_compile" for cs in by_key.values()
                   for c in cs), by_key
