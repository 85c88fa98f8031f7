"""SDAR through the normal serving path, against the benchmark's plain
reference (benchmarks/reference/sdar.py): float32, tiny sizes that keep
every mechanism: two layers, hidden 64, 4 query heads over 2 key/value heads
of 16, 8 experts top-2 renormalised, a vocabulary of 256 with the mask token
at 255, blocks of 4 denoised one token a pass, pages of 4."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import sdar as ref  # noqa: E402

from deeplearning4j_tpu import observe  # noqa: E402
from deeplearning4j_tpu.environment import environment  # noqa: E402
from deeplearning4j_tpu.models.sdar import (  # noqa: E402
    SdarConfig, SdarModel, sdar_cache_rows, sdar_prefill, sdar_programs)
from deeplearning4j_tpu.ops import pallas_attention as pa  # noqa: E402
from deeplearning4j_tpu.ops import registry  # noqa: E402
from deeplearning4j_tpu.serving import GenerativeEngine  # noqa: E402

PAGE = 4
M = 255
# lengths with every tail a block can have: 1, 0, 3 and 3 fixed positions
PROMPT_LENS = (5, 8, 3, 11)


def bench_cfg(**kw):
    """The tiny model as a benchmark configuration (the reference's view),
    with gains that make a block's positions move each other's logits."""
    cfg = dict(
        vocab_size=256, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1000000,
        block_length=4, denoising_steps=4, mask_token_id=M,
        param_dtype="float32",
        init={"embed_sigma": 0.5, "W_q": 0.5, "W_k": 0.5, "qk_norm": 2.0,
              "W_o": 2.0, "router": 1.5, "head": 3.0})
    cfg.update(kw)
    return cfg


PCFG = SdarConfig.tiny()


@pytest.fixture(scope="module")
def model():
    """Weights whose values bfloat16 holds exactly, widened to float32."""
    cfg = bench_cfg()
    narrow = ref.make_weights(cfg, 5, jnp.bfloat16)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), narrow)


def _engine(weights, pcfg=PCFG, **kw):
    geo = dict(max_slots=3, page_size=PAGE, max_pages_per_seq=12,
               max_prompt=16)
    geo.update(kw)
    return GenerativeEngine(SdarModel(pcfg, params=weights), **geo)


def _prompts(vocab=250):
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _sample(prompts, results):
    return [{"prompt": p, "tokens": r.tokens, "unmask_pass": r.unmask_pass}
            for p, r in zip(prompts, results)]


# (a) the program against the reference --------------------------------------


def test_block_steps_through_the_engine_agree_with_the_reference(model):
    """Prefill and then block steps (four slots' worth of requests on three
    slots, so a slot is reused): at every pass the reference's best logit
    is the served token's and the position unmasked is its most confident
    masked one. Each block's masked positions are unmasked one a pass, in
    passes 0, 1, ...; a first block's fixed tail yields nothing."""
    cfg, weights = model
    prompts = _prompts()
    results = _engine(weights).generate(prompts, max_new_tokens=9,
                                        eos_token=-1)
    for p, r in zip(prompts, results):
        assert r.finish_reason == "length" and len(r.tokens) == 9
        first = 4 - len(p) % 4 if len(p) % 4 else 4
        assert sorted(r.unmask_pass[:first]) == list(range(first))
        assert sorted(r.unmask_pass[first:first + 4]) == [0, 1, 2, 3]
    got = ref.served_gaps(cfg, 5, _sample(prompts, results),
                          weights=weights)
    assert got["served_logit_gap"] < 1e-4, got
    assert got["served_order_gap"] < 1e-4, got
    # a truncated last block is replayed up to its first pass that
    # unmasked a token the result cut
    assert 4 * 7 <= got["passes_read"] <= 4 * 9
    assert got["distinct_tokens"] > 8


def test_each_planted_fault_and_lower_precision_is_seen(model):
    """Every stand-in's picks, judged in the program's place, fail by at
    least one of the two gaps by orders of magnitude over the program's."""
    cfg, weights = model
    prompts = _prompts()[:2]
    results = _engine(weights).generate(prompts, max_new_tokens=8,
                                        eos_token=-1)
    sample = _sample(prompts, results)
    for control in ref.CONTROLS + ref.FAULTS:
        got = ref.served_gaps(cfg, 5, sample, weights=weights,
                              control=control)
        assert max(got["control_logit_gap"],
                   got["control_order_gap"]) > 0.05, (control, got)


def test_the_block_causal_prefill_is_the_reference_and_a_causal_mask_is_not(
        model):
    """What prefill caches of a prompt's whole blocks is the reference's
    keys and values over the block-causal mask; with a causal mask inside
    the blocks the second layer's rows move."""
    cfg, weights = model
    ids = np.zeros((16,), np.int32)
    ids[:11] = _prompts()[3]
    rows, stats = sdar_prefill(weights, jnp.asarray(ids)[None], PCFG, 8)
    want = ref.prefill_rows(cfg, weights, ids, 8)
    np.testing.assert_allclose(np.asarray(rows)[:, :, :8],
                               np.asarray(want)[:, :, :8], atol=2e-5)
    causal = ref.prefill_rows(cfg, weights, ids, 8, fault="causal_in_block")
    moved = np.abs(np.asarray(causal) - np.asarray(want))[1, :, :8].max()
    assert moved > 1e-2
    assert int(np.asarray(stats)[:, :-2].sum()) == 2 * 8 * 2


# (b) the kernel ---------------------------------------------------------------


def test_the_kernel_is_the_generic_body():
    """The Pallas kernel (interpreted) against the generic body at lane-wide
    grouped heads over a bfloat16 pool, the shapes the registry takes the
    kernel for: an empty context, a partial last page, a walk past the ring
    (the op's validation case, tests/test_op_validation.py, holds the rest
    against a numpy oracle)."""
    s_n, b, hq, hkv, d, page = 3, 4, 8, 2, 128, 8
    ks = jax.random.split(jax.random.key(9), 5)
    q = jax.random.normal(ks[0], (s_n, b, hq, d), jnp.bfloat16)
    kb, vb = (jax.random.normal(k, (s_n, b, hkv, d), jnp.bfloat16)
              for k in ks[1:3])
    kv = jax.random.normal(ks[3], (2, 2, 40, page, hkv * d), jnp.bfloat16)
    pt = jax.random.permutation(ks[4], 40)[:30].reshape(3, 10).astype(
        jnp.int32)
    sl = jnp.asarray([13, 0, 77], jnp.int32)
    args = (q, kb, vb, kv, pt, sl)
    want = pa.block_decode_attention_xla(*args, layer=1)
    got = pa._block_decode_call(*args, layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    usable = registry().get("block_decode_attention").platform_usable["tpu"]
    assert usable(*args, layer=1)
    assert not usable(q[..., :16], kb[..., :16], vb[..., :16],
                      kv[..., :32], pt, sl, layer=1)


def test_the_kernel_serves_a_lane_wide_model_as_the_reference_does():
    """One layer at heads of 128 lanes: the engine with the kernel
    (interpreted) agrees with the reference, prompt tails and a partial
    last page among the requests."""
    cfg = bench_cfg(num_hidden_layers=1, head_dim=128, num_experts=4)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           ref.make_weights(cfg, 3, jnp.bfloat16))
    pcfg = SdarConfig.tiny(num_hidden_layers=1, head_dim=128, num_experts=4)
    env = environment()
    was = env.helper_mode
    env.helper_mode = "pallas"
    try:
        observe.reset()
        prompts = _prompts()[:3]
        results = _engine(weights, pcfg, page_size=8, max_pages_per_seq=4,
                          max_prompt=16).generate(prompts, max_new_tokens=6,
                                                  eos_token=-1)
    finally:
        env.helper_mode = was
    assert any(k.startswith("block_decode_attention/tpu/")
               for k in observe.dispatch_summary())
    got = ref.served_gaps(cfg, 3, _sample(prompts, results),
                          weights=weights)
    assert got["served_logit_gap"] < 1e-4 and got["served_order_gap"] < 1e-4


# (c) the engine ---------------------------------------------------------------


def test_an_eos_or_the_token_budget_inside_a_block_keeps_what_came_before(
        model):
    """Greedy blocks are what they are whatever stops them: an ``eos`` in
    the middle of a block, or ``max_new_tokens`` reached in one, keeps
    exactly the tokens before it (the eos itself trimmed), drops the rest
    of the block and leaves the pool's accounting sound."""
    _, weights = model
    prompt = _prompts()[1]                      # 8: blocks at 8, 12, 16
    eng = _engine(weights)
    full = eng.generate([prompt], max_new_tokens=12, eos_token=-1)[0].tokens
    cut = int(np.flatnonzero(full == full[5])[0])
    res = eng.generate([prompt], max_new_tokens=12,
                       eos_token=int(full[5]))[0]
    assert res.finish_reason == "eos"
    np.testing.assert_array_equal(res.tokens, full[:cut])
    assert len(res.unmask_pass) == cut
    res = eng.generate([prompt], max_new_tokens=6, eos_token=-1)[0]
    assert res.finish_reason == "length"
    np.testing.assert_array_equal(res.tokens, full[:6])
    eng.check_invariants()
    assert not eng.scheduler.has_work() and eng.cache.free_pages == \
        eng.cache.num_pages


def test_the_step_ahead_worker_serves_what_the_inline_loop_does(model):
    """``start()`` / ``submit`` (a block step in flight while the next is
    launched) gives the same tokens, the same unmask passes and stamps a
    block's tokens together."""
    _, weights = model
    prompts = _prompts()
    inline = _engine(weights).generate(prompts, max_new_tokens=10,
                                       eos_token=-1)
    eng = _engine(weights).start()
    try:
        futs = [eng.submit(p, max_new_tokens=10, eos_token=-1)
                for p in prompts]
        ahead = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    for a, b in zip(inline, ahead):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.unmask_pass, b.unmask_pass)
        assert b.ttft_s is not None and len(b.intertoken_s) == 9
        assert b.intertoken_s.count(0.0) >= 6


def test_spans_counters_and_one_compile(model):
    """The block steps' spans carry the passes, the masked positions and the
    tokens; the counters count passes by kind and blocks; the expert layer
    reports through ``note_moe``; every program compiles once across
    admissions and retirements."""
    _, weights = model
    observe.reset()
    prompts = _prompts()
    eng = _engine(weights)
    eng.generate(prompts, max_new_tokens=9, eos_token=-1)
    events = [e for e in observe.tracer().to_dict()["traceEvents"]
              if e.get("ph") == "X"]
    decodes = [e["args"] for e in events if e["name"] == "serving_decode"]
    prefills = [e["args"] for e in events if e["name"] == "serving_prefill"]
    assert len(prefills) == 4 and decodes
    assert all(a["dd_passes"] == 1 and a["dd_tokens"] == 0 for a in prefills)
    for a in decodes:
        assert 1 < a["dd_passes"] <= 5 and a["dd_masked"] == a["dd_tokens"]
        assert 0 < a["dd_tokens"] <= 4 * a["slots"] and a["moe_held"] > 0
    # a block yields 4 tokens, a first block 4 less its fixed tail; the
    # last block of each request is cut at the budget of 9
    assert sum(a["dd_tokens"] for a in decodes) == 11 + 12 + 9 + 9
    text = observe.metrics().render_prometheus()
    stores = len(decodes)
    assert f'dl4j_tpu_diffusion_passes_total{{kind="store"}} {stores}' in text
    denoise = sum(a["dd_passes"] - 1 for a in decodes)
    assert (f'dl4j_tpu_diffusion_passes_total{{kind="denoise"}} {denoise}'
            in text)
    blocks = sum(a["slots"] for a in decodes)
    assert f"dl4j_tpu_diffusion_blocks_total {blocks}" in text
    by_key = {}
    for ev in observe.ledger().events():
        if ev.graph == "serving":
            by_key.setdefault(ev.key, []).append(ev.cause)
    assert by_key["decode"] == ["first_compile"], by_key
    assert by_key["prefill"] == ["first_compile"], by_key
    assert eng._decode_fn._cache_size() == 1


def test_what_a_block_model_is_refused(model):
    """No prefix cache, no speculation, no sampled request and no prompt
    that holds the mask token; the schedule is one token a pass."""
    _, weights = model
    m = SdarModel(PCFG, params=weights)
    assert sdar_cache_rows(PCFG) == (2, 2, 32)
    assert sdar_programs(PCFG).block == 4
    with pytest.raises(ValueError, match="prefix_pages=4 needs"):
        GenerativeEngine(m, prefix_pages=4)
    with pytest.raises(ValueError, match="spec_k=2 needs a verify"):
        GenerativeEngine(m, spec_k=2, draft_model=m)
    eng = _engine(weights)
    with pytest.raises(ValueError, match="sampled requests"):
        eng.submit(np.arange(1, 6), temperature=0.7)
    with pytest.raises(ValueError, match="mask token 255"):
        eng.submit(np.asarray([3, 255, 4]))
    with pytest.raises(ValueError, match="one token a pass"):
        SdarConfig.tiny(denoising_steps=2)
