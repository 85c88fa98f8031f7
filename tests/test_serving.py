"""Continuous-batching generative serving tests (docs/SERVING.md).

Covers the four properties the subsystem is built around:
  * allocator soundness — the paged KV cache's free-list/page-table
    invariants across alloc/free/fragmentation and mid-flight eviction;
  * numerical equivalence — the Pallas paged decode path reproduces the
    XLA gather fallback (1e-2/1e-5) AND greedy engine output reproduces a
    full-attention autoregressive oracle token-for-token;
  * compile-once — admits/evicts never change the decode jit signature
    (asserted through the PR-6 RecompileLedger);
  * PRNG hygiene — no key value is ever consumed twice across the
    scheduler loop (the graftlint GL004 property, asserted at runtime).
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import models, observe
from deeplearning4j_tpu.models.gpt import (
    GptConfig, GptModel, reference_generate,
)
from deeplearning4j_tpu.ops.pallas_attention import (
    _paged_decode_call, _paged_usable, paged_decode_attention_xla,
)
from deeplearning4j_tpu.ops.registry import registry
from deeplearning4j_tpu.serving import GenerativeEngine, PagedKVCache
from deeplearning4j_tpu.serving.sampling import (
    SAMPLER_PATHS, sample_tokens, sampler_path)

CFG = GptConfig.tiny()
MODEL = GptModel(CFG, seed=1)


def make_engine(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 6)
    kw.setdefault("max_prompt", 16)
    kw.setdefault("seed", 3)
    return GenerativeEngine(MODEL, **kw)


PROMPTS = [np.array([3, 5, 7, 9], np.int32),
           np.array([11, 2], np.int32),
           np.array([42, 43, 44, 45, 46, 47], np.int32),
           np.array([8, 8, 8], np.int32),
           np.array([17, 23, 31], np.int32)]


# ---------------------------------------------------------------------------
# paged KV cache — allocator invariants
# ---------------------------------------------------------------------------


class TestPagedKVCache:
    def make_cache(self, **kw):
        kw.setdefault("layers", 2)
        kw.setdefault("row_width", 16)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 8)
        kw.setdefault("max_slots", 3)
        kw.setdefault("max_pages_per_seq", 4)
        return PagedKVCache(**kw)

    def test_alloc_grow_free_invariants(self):
        c = self.make_cache()
        assert c.free_pages == 8
        assert c.ensure_capacity(0, 5) == "ok"   # 2 pages
        c.check_invariants()
        assert c.free_pages == 6 and len(c.owned[0]) == 2
        assert c.ensure_capacity(0, 6) == "ok"   # still 2 pages
        assert len(c.owned[0]) == 2
        assert c.ensure_capacity(1, 9) == "ok"   # 3 pages
        c.check_invariants()
        assert c.free_pages == 3
        released = c.free_slot(0)
        assert released == 2 and c.free_pages == 5
        c.check_invariants()
        # the freed slot's table row points wholly at the trash page
        assert all(int(p) == c.trash_page for p in c.page_table[0])

    def test_fragmented_reuse(self):
        """Pages freed by a middle slot are reusable by a later alloc — the
        free list doesn't care about contiguity (that's the point of
        paging)."""
        c = self.make_cache()
        for slot, toks in ((0, 8), (1, 8), (2, 8)):
            assert c.ensure_capacity(slot, toks) == "ok"
        assert c.free_pages == 2
        freed = set(c.owned[1])
        c.free_slot(1)
        assert c.ensure_capacity(1, 16) == "ok"  # 4 pages from a torn pool
        c.check_invariants()
        assert freed & set(c.owned[1]), "freed pages were not reused"

    def test_overflow_no_partial_alloc(self):
        c = self.make_cache()
        assert c.ensure_capacity(0, 17) == "overflow"  # 5 pages > 4/seq
        assert c.owned[0] == [] and c.free_pages == 8
        c.check_invariants()

    def test_oom_no_partial_alloc(self):
        c = self.make_cache()
        assert c.ensure_capacity(0, 16) == "ok"
        assert c.ensure_capacity(1, 16) == "ok"
        assert c.ensure_capacity(2, 4) == "oom"  # 0 pages left
        assert c.owned[2] == [] and c.free_pages == 0
        c.check_invariants()
        c.free_slot(0)
        assert c.ensure_capacity(2, 4) == "ok"
        c.check_invariants()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def _oracle(logits, step_key, temperature, top_k, top_p):
    """The sampler as it was before it chose a body (PR 28): everything
    computed for every slot, the result picked by ``where``. With the one
    repair the filter body has: a row with ``top_p >= 1`` takes no cut."""
    s_n, vocab = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    k = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)
    masked = jnp.where(scaled >= kth, scaled, -jnp.inf)
    probs = jax.nn.softmax(masked, axis=-1)
    sp = jnp.sort(probs, axis=-1)[:, ::-1]
    cum = jnp.cumsum(sp, axis=-1)
    keep_sorted = ((cum - sp) < top_p[:, None]) | (top_p >= 1.0)[:, None]
    cutoff = jnp.min(jnp.where(keep_sorted, sp, jnp.inf), axis=-1,
                     keepdims=True)
    masked = jnp.where(probs >= cutoff, masked, -jnp.inf)
    keys = jax.random.split(step_key, s_n)
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


_ORACLE = jax.jit(_oracle)
_SAMPLE = jax.jit(sample_tokens)

# a bank by its rows: g(reedy), p(lain: temperature alone), f(ilter: top-k
# and/or top-p)
_BANKS = {"all_greedy": "gggggg", "all_plain": "pppppp",
          "all_filter": "ffffff", "greedy_plain": "gpgpgp",
          "greedy_filter": "gfgfgf", "plain_filter": "pfpfpf",
          "all_three": "gpfgpf"}


def _knobs(rows):
    """(temperature, top_k, top_p) for a bank given as a string of g/p/f;
    the filter rows go through top-k alone, top-p alone and both."""
    temp, top_k, top_p = [], [], []
    filters = [(5, 1.0), (0, 0.7), (9, 0.9)]
    for i, kind in enumerate(rows):
        k, p = filters[i % 3] if kind == "f" else (0, 1.0)
        temp.append(0.0 if kind == "g" else 0.7 + 0.2 * i)
        top_k.append(k)
        top_p.append(p)
    return (np.asarray(temp, np.float32), np.asarray(top_k, np.int32),
            np.asarray(top_p, np.float32))


class TestSampling:
    def logits(self, s=4, v=32, seed=0):
        return jnp.asarray(np.random.RandomState(seed).randn(s, v)
                           .astype(np.float32))

    def test_greedy_when_temperature_zero(self):
        lg = self.logits()
        toks = sample_tokens(lg, jax.random.key(0),
                             jnp.zeros(4), jnp.zeros(4, jnp.int32),
                             jnp.ones(4))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(jnp.argmax(lg, -1)))

    def test_top_k_one_is_greedy(self):
        lg = self.logits()
        toks = sample_tokens(lg, jax.random.key(1),
                             jnp.full(4, 2.0), jnp.ones(4, jnp.int32),
                             jnp.ones(4))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(jnp.argmax(lg, -1)))

    def test_top_p_tiny_is_greedy(self):
        lg = self.logits()
        toks = sample_tokens(lg, jax.random.key(2),
                             jnp.full(4, 2.0), jnp.zeros(4, jnp.int32),
                             jnp.full(4, 1e-6))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(jnp.argmax(lg, -1)))

    def test_top_k_restricts_support(self):
        lg = self.logits(s=2, v=16)
        top3 = np.asarray(jnp.argsort(lg, axis=-1)[:, -3:])
        for seed in range(20):
            toks = np.asarray(sample_tokens(
                lg, jax.random.key(seed), jnp.full(2, 1.5),
                jnp.full(2, 3, jnp.int32), jnp.ones(2)))
            for row in range(2):
                assert toks[row] in top3[row]

    def test_slots_sample_independently(self):
        """Identical logits rows must NOT force identical samples — each
        slot consumes its own split of the step key."""
        lg = jnp.zeros((8, 64))  # uniform
        toks = np.asarray(sample_tokens(
            lg, jax.random.key(5), jnp.ones(8), jnp.zeros(8, jnp.int32),
            jnp.ones(8)))
        assert len(set(toks.tolist())) > 1

    def test_mixed_greedy_and_sampled_slots(self):
        lg = self.logits()
        temp = jnp.asarray([0.0, 1.0, 0.0, 1.0])
        toks = np.asarray(sample_tokens(lg, jax.random.key(3), temp,
                                        jnp.zeros(4, jnp.int32),
                                        jnp.ones(4)))
        greedy = np.asarray(jnp.argmax(lg, -1))
        assert toks[0] == greedy[0] and toks[2] == greedy[2]

    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("vocab", [32, 50257])
    @pytest.mark.parametrize("bank", sorted(_BANKS))
    def test_tokens_equal_the_oracles(self, bank, vocab, seed):
        """Whichever body the bank's knobs choose, every slot's token is
        the one the sampler that computes everything gives it."""
        temp, top_k, top_p = _knobs(_BANKS[bank])
        path = SAMPLER_PATHS[int(sampler_path(temp, top_k, top_p))]
        assert path == ("filter" if "f" in _BANKS[bank] else
                        "plain" if "p" in _BANKS[bank] else "greedy")
        lg = self.logits(s=len(temp), v=vocab, seed=seed)
        args = (lg, jax.random.key(100 + seed), jnp.asarray(temp),
                jnp.asarray(top_k), jnp.asarray(top_p))
        np.testing.assert_array_equal(np.asarray(_SAMPLE(*args)),
                                      np.asarray(_ORACLE(*args)))

    @pytest.mark.parametrize("row", ["g", "p", "f"])
    def test_a_slots_token_does_not_depend_on_its_neighbours(self, row):
        """One row (logits, key position, knobs) in a bank of greedy rows
        and beside a top-k/top-p row: the same token, though the bank takes
        another body."""
        lg = self.logits(s=4, v=50257, seed=7)
        alone, beside = _knobs(row + "ggg"), _knobs(row + "gfg")
        assert (int(sampler_path(*alone)) != int(sampler_path(*beside))
                or row == "f")
        for seed in range(5):
            key = jax.random.key(40 + seed)
            a = np.asarray(_SAMPLE(lg, key, *map(jnp.asarray, alone)))
            b = np.asarray(_SAMPLE(lg, key, *map(jnp.asarray, beside)))
            assert a[0] == b[0]
            greedy = np.asarray(jnp.argmax(lg, -1))
            assert (a[[1, 3]] == greedy[[1, 3]]).all()
            assert (b[[1, 3]] == greedy[[1, 3]]).all()

    def test_top_p_one_keeps_the_tail(self):
        """A row of 50257 near-uniform logits that asked for no nucleus cut
        (``top_p = 1``) can draw ids from the last sorted thousandth, where
        a float32 cumulative sum that rounds past 1 would have cut it. The
        filter body is forced by a top-k row beside it; the keys are those
        whose unfiltered draw lands in that tail (one in a thousand)."""
        v = 50257
        rng = np.random.RandomState(11)
        lg = jnp.asarray(np.stack([rng.uniform(0.0, 1e-3, v),
                                   rng.randn(v)]).astype(np.float32))
        temp = jnp.asarray([1.0, 1.0], jnp.float32)
        top_k = jnp.asarray([0, 5], jnp.int32)
        top_p = jnp.asarray([1.0, 1.0], jnp.float32)
        assert SAMPLER_PATHS[int(sampler_path(temp, top_k, top_p))] == \
            "filter"
        tail = np.argsort(-np.asarray(lg[0]), kind="stable")[-(v // 1000):]

        @jax.jit
        def unfiltered(seeds):  # row 0's draw under each seed's step key
            return jax.vmap(lambda seed: jax.random.categorical(
                jax.random.split(jax.random.key(seed), 2)[0], lg[0]))(seeds)

        hits = []
        for start in range(0, 8000, 1000):
            toks = np.asarray(unfiltered(jnp.arange(start, start + 1000)))
            hits += [(start + int(i), int(toks[i]))
                     for i in np.flatnonzero(np.isin(toks, tail))]
            if len(hits) >= 2:
                break
        assert len(hits) >= 2
        for seed, tok in hits:
            got = _SAMPLE(lg, jax.random.key(seed), temp, top_k, top_p)
            assert int(got[0]) == tok


# ---------------------------------------------------------------------------
# paged decode numerics: Pallas vs XLA gather fallback
# ---------------------------------------------------------------------------


def _numpy_paged_attention(q, kv, pt, sl, layer):
    """Straight per-head softmax over each slot's first ``sl`` tokens."""
    s_n, h, d = q.shape
    want = np.zeros_like(q)
    for i in range(s_n):
        gk = kv[layer, 0, pt[i]].reshape(-1, h, d)[:sl[i]]
        gv = kv[layer, 1, pt[i]].reshape(-1, h, d)[:sl[i]]
        for hh in range(h):
            sc = gk[:, hh] @ q[i, hh] / np.sqrt(d)
            p = np.exp(sc - sc.max())
            want[i, hh] = (p / p.sum()) @ gv[:, hh]
    return want


@contextlib.contextmanager
def helper_mode(mode):
    from deeplearning4j_tpu.environment import environment

    env = environment()
    old = env.helper_mode
    env.helper_mode = mode
    try:
        yield
    finally:
        env.helper_mode = old


def _dispatches(op="paged_decode_attention"):
    return {k: v for k, v in observe.dispatch_summary().items()
            if k.startswith(op + "/")}


WIDE_CFG = GptConfig.tiny(hidden=128)   # 4 heads x 32: the kernel's width
WIDE_MODEL = GptModel(WIDE_CFG, seed=1)


class TestPagedDecodeEquivalence:
    # (heads, head_dim, page, pages a slot, kernel takes it): the merged
    # width has to be a multiple of 128 lanes and the page of 8 sublanes. 19
    # pages a slot is three of the kernel's groups of 8 pages, the last one
    # short, with sequences that end inside the first, second and third.
    @pytest.mark.parametrize("h,d,page,max_pages,taken", [
        (4, 32, 8, 4, True), (4, 64, 8, 19, True), (12, 64, 16, 19, True),
        (4, 16, 8, 4, False), (2, 64, 4, 19, False)])
    def test_kernel_and_fallback_match_numpy_oracle(self, h, d, page,
                                                    max_pages, taken):
        r = np.random.RandomState(3)
        s_n, n_pages, layer = 4, 5 * max_pages, 1
        q = r.randn(s_n, h, d).astype(np.float32)
        kv = r.randn(2, 2, n_pages, page, h * d).astype(np.float32)
        pt = np.stack([r.choice(n_pages, max_pages, replace=False)
                       for _ in range(s_n)]).astype(np.int32)
        sl = np.array([1, max_pages * page // 2 + 1,
                       (max_pages - 1) * page + 1, max_pages * page],
                      np.int32)
        want = _numpy_paged_attention(q, kv, pt, sl, layer)
        args = tuple(jnp.asarray(a) for a in (q, kv, pt, sl))
        got = paged_decode_attention_xla(*args, layer=layer)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-5)
        assert _paged_usable(*args, layer=layer) is taken
        desc = registry().get("paged_decode_attention")
        with helper_mode("pallas"):
            impl = desc.resolve(*args, layer=layer)
        assert (impl is not desc.fn) is taken
        got = impl(*args, layer=layer)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-5)

    def test_bfloat16_pool_kernel_matches_fallback(self):
        r = np.random.RandomState(4)
        q = jnp.asarray(r.randn(3, 4, 32), jnp.bfloat16)
        kv = jnp.asarray(r.randn(1, 2, 7, 16, 128), jnp.bfloat16)
        pt = jnp.asarray(r.randint(0, 7, (3, 3)).astype(np.int32))
        sl = jnp.asarray(np.array([0, 17, 48], np.int32))
        want = paged_decode_attention_xla(q, kv, pt, sl)
        got = _paged_decode_call(q, kv, pt, sl, interpret=True)
        # slot 0 is inactive (length 0): its output is never read
        np.testing.assert_allclose(np.asarray(got[1:], np.float32),
                                   np.asarray(want[1:], np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_greedy_engine_equivalence_pallas_vs_xla(self):
        """Whole-loop equivalence at a width the kernel takes: greedy
        generation with the registry resolving the Pallas paged path
        (forced helper_mode, interpret on CPU) must emit the SAME tokens as
        the XLA gather fallback."""
        def run():
            eng = GenerativeEngine(WIDE_MODEL, max_slots=2, page_size=8,
                                   max_pages_per_seq=6, max_prompt=16, seed=3)
            return [r.tokens for r in
                    eng.generate(PROMPTS[:3], max_new_tokens=6)]

        with helper_mode("xla"):
            xla_toks = run()
        before = _dispatches()
        with helper_mode("pallas"):
            pallas_toks = run()
        took = {k: v - before.get(k, 0) for k, v in _dispatches().items()}
        assert took.get("paged_decode_attention/tpu/usable") == \
            WIDE_CFG.layers, took
        for a, b in zip(xla_toks, pallas_toks):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("model,mode", [(MODEL, "auto"),
                                            (WIDE_MODEL, "pallas")],
                             ids=["narrow-generic", "wide-kernel"])
    @pytest.mark.parametrize("p_len", [5, 8, 13, 20])
    def test_whole_page_prompt_write_then_decode_matches_oracle(
            self, model, mode, p_len):
        """``write_prompt`` writes whole pages: a prompt that ends mid-page
        leaves the padded positions' rows in the tail of its last page.
        Attention must mask them and decode overwrite them, so the tokens
        that follow (here across the next page boundary, with a prompt
        bucket that is no multiple of the page) are the oracle's."""
        prompt = (np.arange(p_len, dtype=np.int32) * 7 + 3) % 200 + 1
        with helper_mode(mode):
            eng = GenerativeEngine(model, max_slots=2, page_size=8,
                                   max_pages_per_seq=5, max_prompt=20,
                                   seed=3)
            res = eng.generate([prompt], max_new_tokens=12)[0]
        assert res.finish_reason == "length"
        np.testing.assert_array_equal(
            res.tokens, reference_generate(model.params, model.cfg, prompt,
                                           12))
        eng.cache.check_invariants()

    def test_greedy_matches_full_attention_oracle(self):
        """Paged decode vs an O(T²) full-prefill autoregressive oracle —
        token-for-token, across slot counts and mid-flight admits."""
        eng = make_engine(max_slots=2)
        results = eng.generate(PROMPTS, max_new_tokens=5)
        for prompt, res in zip(PROMPTS, results):
            assert res.finish_reason == "length"
            want = reference_generate(MODEL.params, CFG, prompt, 5)
            np.testing.assert_array_equal(res.tokens, want)
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages


# ---------------------------------------------------------------------------
# continuous batching: admit/evict mid-flight
# ---------------------------------------------------------------------------


class TestContinuousBatching:
    def test_admit_evict_midflight(self):
        """5 requests through 2 slots with different budgets: slots must
        turn over mid-flight, every result must still match the oracle,
        and every page must come home."""
        observe.reset()
        eng = make_engine(max_slots=2)
        budgets = [3, 8, 2, 6, 4]
        futs = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(PROMPTS, budgets)]
        while eng.scheduler.has_work():
            eng.step()
        for p, b, f in zip(PROMPTS, budgets, futs):
            res = f.result(timeout=0)
            assert res.finish_reason == "length"
            np.testing.assert_array_equal(
                res.tokens, reference_generate(MODEL.params, CFG, p, b))
        m = observe.metrics()
        assert m.counter("dl4j_tpu_serving_admitted_total").value == 5
        assert m.family_total("dl4j_tpu_serving_evicted_total") == 5
        assert m.counter(
            "dl4j_tpu_serving_generated_tokens_total").value == sum(budgets)
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_eos_finishes_early(self):
        """Whatever greedy decode emits first becomes the eos token of a
        second run — which must then stop immediately after it."""
        probe = make_engine().generate([PROMPTS[0]], max_new_tokens=3)[0]
        eos = int(probe.tokens[0])
        res = make_engine().generate([PROMPTS[0]], max_new_tokens=10,
                                     eos_token=eos)[0]
        assert res.finish_reason == "eos"
        assert res.tokens.size == 0  # eos was the first token; excluded

    def test_overflow_eviction(self):
        """A sequence that outgrows its page-table row is evicted with its
        partial output — which must equal the oracle prefix."""
        eng = make_engine(max_slots=1, page_size=4, max_pages_per_seq=3,
                          max_prompt=8)  # context cap: 12 tokens
        prompt = PROMPTS[0]  # 4 tokens
        res = eng.generate([prompt], max_new_tokens=50)[0]
        assert res.finish_reason == "overflow"
        # capacity 12: 4 prompt + 8 cached tokens; the 9th token was
        # sampled but its K/V had nowhere to land
        assert res.tokens.size == 9
        np.testing.assert_array_equal(
            res.tokens, reference_generate(MODEL.params, CFG, prompt, 9))
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_oom_eviction_returns_pages(self):
        """An oversubscribed pool (2 slots × 4 pages/seq, 5 pages total)
        must evict under pressure, return the pages, and keep serving."""
        observe.reset()
        eng = make_engine(max_slots=2, page_size=4, max_pages_per_seq=4,
                          num_pages=5, max_prompt=8)
        res = eng.generate([PROMPTS[0], PROMPTS[3]], max_new_tokens=12)
        reasons = sorted(r.finish_reason for r in res)
        assert "oom" in reasons, reasons
        # the survivor must have completed its full budget
        assert "length" in reasons, reasons
        for prompt, r in zip([PROMPTS[0], PROMPTS[3]], res):
            np.testing.assert_array_equal(
                r.tokens,
                reference_generate(MODEL.params, CFG, prompt,
                                   len(r.tokens)))
        assert observe.metrics().counter(
            "dl4j_tpu_serving_evicted_total", reason="oom").value >= 1
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_threaded_serving_loop(self):
        """start()/submit()/stop() — the ParallelInference lifecycle."""
        eng = make_engine(max_slots=2).start()
        try:
            futs = [eng.submit(p, max_new_tokens=4) for p in PROMPTS[:4]]
            for p, f in zip(PROMPTS, futs):
                res = f.result(timeout=120)
                np.testing.assert_array_equal(
                    res.tokens, reference_generate(MODEL.params, CFG, p, 4))
        finally:
            eng.stop()

    def test_parallel_inference_facade(self):
        from deeplearning4j_tpu.parallel.mesh import ParallelInference

        eng = ParallelInference.generative(MODEL, max_slots=2, page_size=8,
                                           max_pages_per_seq=6,
                                           max_prompt=16)
        assert isinstance(eng, GenerativeEngine)
        res = eng.generate([PROMPTS[1]], max_new_tokens=3)[0]
        np.testing.assert_array_equal(
            res.tokens, reference_generate(MODEL.params, CFG, PROMPTS[1], 3))

    def test_oversized_prompt_rejected(self):
        eng = make_engine(max_prompt=8)
        with pytest.raises(ValueError, match="prefill bucket"):
            eng.submit(np.arange(9, dtype=np.int32))

    def test_max_prompt_beyond_positions_rejected(self):
        with pytest.raises(ValueError, match="max_position"):
            make_engine(max_prompt=CFG.max_position + 1,
                        max_pages_per_seq=64)

    def test_submit_after_stop_rejected(self):
        eng = make_engine().start()
        eng.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit(PROMPTS[0])

    def test_out_of_vocab_prompt_rejected(self):
        eng = make_engine()
        with pytest.raises(ValueError, match="token ids"):
            eng.submit(np.array([CFG.vocab_size], np.int32))
        with pytest.raises(ValueError, match="token ids"):
            eng.submit(np.array([-1], np.int32))

    def test_stop_delivers_partial_results_as_stopped(self):
        """stop() mid-generation retires in-flight slots with reason
        'stopped' and their partial tokens — not a bare exception."""
        eng = make_engine(max_slots=1)
        fut = eng.submit(PROMPTS[0], max_new_tokens=50)
        eng.step()  # admit + first decode: at least 2 tokens exist
        eng.stop()
        res = fut.result(timeout=0)
        assert res.finish_reason == "stopped"
        assert res.tokens.size >= 1
        np.testing.assert_array_equal(
            res.tokens,
            reference_generate(MODEL.params, CFG, PROMPTS[0],
                               len(res.tokens)))
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_bad_sampling_knobs_rejected(self):
        eng = make_engine()
        with pytest.raises(ValueError, match="top_p"):
            eng.submit(PROMPTS[0], top_p=0.0)  # would degenerate to id 0
        with pytest.raises(ValueError, match="top_k"):
            eng.submit(PROMPTS[0], top_k=-1)

    def test_eos_at_page_boundary_retires_as_eos(self):
        """A slot whose LAST decode emitted eos while sitting at a page
        boundary must retire as 'eos' (trimmed), not grab a capacity page
        or get mis-retired as oom/overflow."""
        probe = make_engine(max_slots=1).generate(
            [np.arange(1, 8, dtype=np.int32)], max_new_tokens=3)[0]
        eos = int(probe.tokens[1])  # second generated token
        eng = make_engine(max_slots=1, page_size=8)
        # prompt 7 tokens: after first decode seq_len=8 == page boundary;
        # the eos arrives exactly there
        res = eng.generate([np.arange(1, 8, dtype=np.int32)],
                           max_new_tokens=10, eos_token=eos)[0]
        assert res.finish_reason == "eos"  # not oom/overflow at the boundary
        assert eos not in res.tokens.tolist()  # trimmed
        assert res.tokens.size < probe.tokens.size + 1
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_page_aligned_prompt(self):
        """Regression: admission must allocate pages for prompt + 1 — with
        a page-aligned prompt the SAME iteration's decode writes the first
        generated token's K/V at position p_len, which otherwise lands on
        the trash page and is permanently lost (later steps attend to a
        zeroed page at that position). Asserted white-box: after the first
        decode the next page must be real and hold nonzero K/V."""
        eng = make_engine(max_slots=1, page_size=8)
        prompt = np.arange(1, 9, dtype=np.int32)  # 8 == page_size exactly
        fut = eng.submit(prompt, max_new_tokens=4)
        eng.step()  # admit + prefill + first decode (writes position 8)
        slot = eng.scheduler.active_slots()[0]
        page1 = int(eng.cache.page_table[slot, 1])
        assert page1 != eng.cache.trash_page, (
            "admission did not allocate the page the first decode writes")
        pos8_kv = np.asarray(eng.cache.kv[:, :, page1, 0])  # (L, 2, H*Dh)
        assert np.abs(pos8_kv).max() > 0, (
            "first generated token's K/V was lost to the trash page")
        while eng.scheduler.has_work():
            eng.step()
        res = fut.result(timeout=0)
        np.testing.assert_array_equal(
            res.tokens, reference_generate(MODEL.params, CFG, prompt, 4))
        eng.cache.check_invariants()


# ---------------------------------------------------------------------------
# compile-once: jit-signature stability across admits/evicts
# ---------------------------------------------------------------------------


class TestDecodeJitStability:
    def test_one_compile_across_admits_and_evicts(self):
        observe.reset()
        eng = make_engine(max_slots=2)
        eng.generate(PROMPTS, max_new_tokens=4)  # 5 reqs > 2 slots: turnover
        serving = [e for e in observe.ledger().events()
                   if e.graph == "serving"]
        by_key = {}
        for ev in serving:
            by_key.setdefault(ev.key, []).append(ev.cause)
        assert by_key["decode"] == ["first_compile"], by_key
        assert by_key["prefill"] == ["first_compile"], by_key
        assert not any("new_shape" in causes for causes in by_key.values())


# ---------------------------------------------------------------------------
# PRNG hygiene: no key reuse across the scheduler loop (GL004 at runtime)
# ---------------------------------------------------------------------------


class TestPrngHygiene:
    def test_no_key_reuse_across_loop(self):
        eng = make_engine(max_slots=2, seed=11)
        eng.generate(PROMPTS, max_new_tokens=5)
        trail = list(eng.key_trail)
        # every prefill and every decode step consumed exactly one fresh key
        assert len(trail) >= len(PROMPTS) + 5
        assert len(set(trail)) == len(trail), (
            "a PRNG key value was issued twice across the scheduler loop")

    def test_keys_split_ahead_are_the_chain_one_at_a_time(self):
        """The engine splits keys while the device runs a step; what it
        hands out is still split(split(...)) of the seed, in order."""
        eng = make_engine(max_slots=2, seed=11)
        eng.generate(PROMPTS, max_new_tokens=5)
        assert eng._key_reserve, "no key was split ahead"
        key, want = jax.random.key(11), []
        for _ in eng.key_trail:
            key, sub = jax.random.split(key)
            want.append(np.asarray(jax.random.key_data(sub)).tobytes())
        assert eng.key_trail == want

    def test_an_unchanged_argument_is_sent_once(self):
        eng = make_engine(max_slots=2)
        eng.generate(PROMPTS[:2], max_new_tokens=4)
        held = eng._resident_args["temperature"][1]
        same = eng._resident("temperature", np.zeros((2,), np.float32))
        assert same is held
        other = eng._resident("temperature", np.ones((2,), np.float32))
        assert other is not held
        np.testing.assert_array_equal(np.asarray(other), np.ones((2,)))

    def test_sampling_differs_across_steps(self):
        """Same slot, same logits landscape, successive steps: sampled
        continuations must not be locked to one token by key reuse."""
        eng = make_engine(max_slots=1, seed=12)
        res = eng.generate([PROMPTS[2]], max_new_tokens=24,
                           temperature=1.5, top_k=0, top_p=1.0)[0]
        assert len(set(res.tokens.tolist())) > 1


# ---------------------------------------------------------------------------
# zoo / hub / serde registration
# ---------------------------------------------------------------------------


class TestGptRegistration:
    def test_zoo_listing(self):
        assert hasattr(models, "GPT")
        m = models.GPT("tiny", seed=2).init()
        assert isinstance(m, GptModel)
        with pytest.raises(ValueError, match="preset"):
            models.GPT("huge")

    def test_config_round_trip(self):
        cfg = GptConfig.tiny(vocab_size=300, eos_token=7)
        assert GptConfig.from_json(cfg.to_json()) == cfg

    def test_hub_round_trip(self, tmp_path):
        hub = models.ModelHub(root=str(tmp_path))
        hub.publish("gpt-t", MODEL, metadata={"purpose": "test"})
        assert "gpt-t" in hub.list_models()
        assert hub.manifest("gpt-t")["kind"] == "GptModel"
        loaded = hub.load("gpt-t")
        assert isinstance(loaded, GptModel) and loaded.cfg == CFG
        ids = np.array([[3, 1, 4]], np.int32)
        np.testing.assert_allclose(loaded.logits(ids), MODEL.logits(ids),
                                   rtol=1e-6, atol=1e-6)

    def test_serde_preserves_dtype(self, tmp_path):
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.gpt import restore_gpt, save_gpt

        m = GptModel(CFG, seed=4, dtype=jnp.bfloat16)
        p = str(tmp_path / "bf16.zip")
        save_gpt(m, p)
        loaded = restore_gpt(p)
        leaf = jax.tree.leaves(loaded.params)[0]
        assert leaf.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(m.params)[0], np.float32),
            np.asarray(jax.tree.leaves(loaded.params)[0], np.float32))

    def test_serde_detects_mismatch(self, tmp_path):
        import zipfile

        from deeplearning4j_tpu.models.gpt import restore_gpt, save_gpt

        p = str(tmp_path / "m.zip")
        save_gpt(MODEL, p)
        with zipfile.ZipFile(p) as z:
            cfg_json = z.read("configuration.json").decode()
            coeff = z.read("coefficients.bin")
        with zipfile.ZipFile(p, "w") as z:  # truncate the buffer
            z.writestr("configuration.json", cfg_json)
            z.writestr("coefficients.bin", coeff[:-8])
        with pytest.raises(ValueError, match="mismatch"):
            restore_gpt(p)
