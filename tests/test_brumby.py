"""Brumby through the normal serving path, against the benchmark's plain
reference (benchmarks/reference/brumby.py): float32, tiny sizes that keep
every mechanism: two layers, hidden 64, 4 query heads over 2 key/value heads
of 16 (136 symmetric products a head, 144 as the program lays them out), a
gate whose decay sits near 0.98, the rotation in half-split pairs."""

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

from reference import brumby as ref  # noqa: E402

from deeplearning4j_tpu import faults, observe  # noqa: E402
from deeplearning4j_tpu.models import mla  # noqa: E402
from deeplearning4j_tpu.models.brumby import (  # noqa: E402
    BrumbyConfig, BrumbyModel, brumby_decode_step, brumby_prefill,
    brumby_slot_state, retention_inputs)
from deeplearning4j_tpu.models.served import SlotState  # noqa: E402
from deeplearning4j_tpu.ops import exec_op, registry  # noqa: E402
from deeplearning4j_tpu.ops import pallas_retention as pr  # noqa: E402
from deeplearning4j_tpu.serving import GenerativeEngine  # noqa: E402
from deeplearning4j_tpu.serving.cache import SlotStatePool  # noqa: E402
from deeplearning4j_tpu.serving.engine import (  # noqa: E402
    build_state_decode, build_state_write)

PAGE = 4


def bench_cfg(**kw):
    """The tiny model as a benchmark configuration (the reference's view)."""
    cfg = dict(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000,
        retention_eps=1e-6, state_dtype="float32", param_dtype="float32",
        init={"embed_sigma": 0.5, "W_o": 3.0, "W_g": 1.0, "gate_bias": 4.0})
    cfg.update(kw)
    return cfg


PCFG = BrumbyConfig.tiny()
ST = ref._static(bench_cfg())


@pytest.fixture(scope="module")
def model():
    """Weights whose values bfloat16 holds exactly, widened to float32: the
    program in float32 and in bfloat16 then differ in arithmetic alone."""
    cfg = bench_cfg()
    narrow = ref.make_weights(cfg, 5, jnp.bfloat16)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), narrow), narrow


def _engine(weights, **kw):
    geo = dict(max_slots=3, page_size=PAGE, max_pages_per_seq=10,
               max_prompt=24)
    geo.update(kw)
    return GenerativeEngine(BrumbyModel(PCFG, params=weights), **geo)


def _vectors(seed, t, hq=4, hkv=2, d=16):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (t, hq, d)),
            jax.random.normal(ks[1], (t, hkv, d)),
            jax.random.normal(ks[2], (t, hkv, d)),
            jnp.log(jax.random.uniform(ks[3], (t, hkv), minval=0.8,
                                       maxval=0.999)))


# (a) the power -------------------------------------------------------------


@pytest.mark.parametrize("d", [16, 128])
def test_phi_is_the_symmetric_second_power(d):
    """``phi(q) . phi(k) = (q . k)^2 / d`` in ``d (d + 2) / 2`` values: the
    ``d (d + 1) / 2`` symmetric products and the ``d / 2`` pairs half a turn
    apart once more (the last row holds each twice, at weight 1)."""
    q, k = (jax.random.normal(key, (7, d))
            for key in jax.random.split(jax.random.key(d), 2))
    pq, pk = pr.power_phi(q), pr.power_phi(k)
    assert pq.shape == (7, pr.phi_rows(d), d) == (7, d // 2 + 1, d)
    assert pq[0].size == d * (d + 1) // 2 + d // 2
    want = np.sum(np.asarray(q, np.float64) * np.asarray(k, np.float64),
                  -1) ** 2 / d
    np.testing.assert_allclose(np.asarray(jnp.sum(pq * pk, (-1, -2))), want,
                               rtol=1e-5)
    # every unordered pair lies somewhere
    seen = {frozenset((a, (a + o) % d))
            for o in range(d // 2 + 1) for a in range(d)}
    assert len(seen) == d * (d + 1) // 2
    with pytest.raises(ValueError, match="even"):
        pr.phi_rows(15)


def test_the_recurrence_equals_the_quadratic_form():
    """The program's two forms of the function, and the reference's two,
    agree on a sequence: a prefill of the first positions (end padding
    masked), then the state's recurrence a token at a time. float32 sums in
    two orders: 2e-4."""
    t, t0, pad = 13, 6, 3
    q, k, v, g = _vectors(0, t)
    want = np.asarray(ref.quadratic_form(q, k, v, g, t, ST, None))
    np.testing.assert_allclose(
        np.asarray(ref.recurrence(q, k, v, g, ST, jnp.float32)), want,
        rtol=2e-4, atol=2e-5)
    padded = lambda x: jnp.concatenate(  # noqa: E731
        [x[:t0], 7.0 * jnp.ones((pad,) + x.shape[1:], x.dtype)])
    y, state, norm, den = exec_op(
        "power_retention_prefill", padded(q), padded(k), padded(v), padded(g),
        jnp.arange(t0 + pad) < t0, eps=1e-6)
    np.testing.assert_allclose(np.asarray(y[:t0]), want[:t0], rtol=2e-4,
                               atol=2e-5)
    assert float(jnp.min(den[:t0])) > 0
    pool, pool_n = state[None, None], norm[None, None]
    for i in range(t0, t):
        pool, pool_n, yi, den_i, amax = exec_op(
            "power_retention_decode", pool, pool_n, q[i:i + 1], k[i:i + 1],
            v[i:i + 1], g[i:i + 1], jnp.ones((1,), bool), layer=0, eps=1e-6)
        np.testing.assert_allclose(np.asarray(yi[0]), want[i], rtol=2e-4,
                                   atol=2e-5)
        assert float(amax) == pytest.approx(float(jnp.max(jnp.abs(pool))))


def test_padded_prompt_positions_leave_no_trace_in_the_state():
    """The state after a prompt padded to the bucket is the unpadded
    prompt's, whatever lies in the padding."""
    t0 = 7
    q, k, v, g = _vectors(1, t0)
    _, clean, clean_n, _ = pr.power_retention_prefill_xla(
        q, k, v, g, jnp.ones((t0,), bool))
    for fill in (0.0, 9.0, -9.0):
        padded = lambda x: jnp.concatenate(  # noqa: E731
            [x, jnp.full((5,) + x.shape[1:], fill, x.dtype)])
        _, state, norm, _ = pr.power_retention_prefill_xla(
            padded(q), padded(k), padded(v), padded(g),
            jnp.arange(t0 + 5) < t0)
        np.testing.assert_allclose(np.asarray(state), np.asarray(clean),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(norm), np.asarray(clean_n),
                                   rtol=1e-6, atol=1e-6)


def test_the_kernel_is_the_generic_body():
    """The Pallas kernel (interpreted) against the generic body at lane-wide
    heads: the pool's other layer and an inactive slot keep every bit, the
    active slots' states and the reads agree to float32 rounding. The
    registry takes the kernel only for such shapes."""
    s_n, layers, hq, hkv, d, dv = 3, 2, 4, 2, 128, 16
    ks = jax.random.split(jax.random.key(3), 6)
    state = jax.random.normal(ks[0], (s_n, layers, hkv, pr.phi_rows(d), dv, d))
    norm = jax.random.uniform(ks[1], (s_n, layers, hkv, pr.phi_rows(d), d))
    q, k, v, g = _vectors(4, s_n, hq, hkv, d)
    v = v[..., :dv]
    on = jnp.asarray([1, 0, 1])
    want = pr.power_retention_decode_xla(state, norm, q, k, v, g, on, layer=1)
    got = pr.power_retention_decode_pallas(state, norm, q, k, v, g, on,
                                           layer=1, interpret=True)
    for before, w, x in zip((state, norm), want[:2], got[:2]):
        # one rounding: the interpreter and XLA's CPU fuse the multiply-add
        # differently (on the v5e the two agree to the last bit: PERF.md)
        np.testing.assert_allclose(np.asarray(x), np.asarray(w), rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(x[:, 0]),
                                      np.asarray(before[:, 0]))
        np.testing.assert_array_equal(np.asarray(x[1]), np.asarray(before[1]))
    for w, x in zip(want[2:4], got[2:4]):
        np.testing.assert_allclose(np.asarray(x)[[0, 2]],
                                   np.asarray(w)[[0, 2]], rtol=2e-4,
                                   atol=2e-4)
    assert float(got[4]) == pytest.approx(float(want[4]), rel=1e-6)
    usable = registry().get("power_retention_decode").platform_usable["tpu"]
    assert usable(state, norm, q, k, v, g, on, layer=1)
    small = _vectors(4, s_n)
    assert not usable(jnp.zeros((s_n, 2, 2, 9, 16, 16)),
                      jnp.zeros((s_n, 2, 2, 9, 16)), *small, on, layer=1)


# (b) the model ---------------------------------------------------------------


def test_prefill_logits_equal_the_reference_and_bfloat16_does_not(model):
    """Tolerance 2e-4: float32 sums in two orders over logits of unit size.
    The same program over the same values in bfloat16 misses it by 100x."""
    cfg, weights, narrow = model
    ids = np.random.default_rng(0).integers(1, 96, (1, 13), dtype=np.int32)
    at = np.arange(13)[None]
    logits, state, stats = brumby_prefill(weights, jnp.asarray(ids), PCFG)
    want = np.asarray(ref.logits_at(cfg, 5, ids, at, weights=weights))
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-4, atol=2e-4)
    assert logits.dtype == jnp.float32
    geo = brumby_slot_state(PCFG)
    assert isinstance(geo, SlotState)
    assert {n: (a.shape, a.dtype.name) for n, a in state.items()} == {
        "S": ((2, 2, 9, 16, 16), "float32"), "z": ((2, 2, 9, 16), "float32")
    } == {n: (s, str(d)) for n, (s, d) in geo.arrays.items()}
    den_min, absmax, decay = (float(x) for x in stats["retention"])
    assert den_min > 0 and absmax > 0 and 0.9 < decay < 1.0
    low, _, _ = brumby_prefill(narrow, jnp.asarray(ids), PCFG)
    assert np.abs(np.asarray(low) - want).max() > 2e-2


def test_one_decode_step_equals_the_quadratic_form(model):
    """One decode step over a state written by the prefill gives the logits
    the prefill gives for the same token at the same position; the slot
    that sits the step out keeps its state to the last bit."""
    cfg, weights, _ = model
    t = 11
    ids = np.random.default_rng(1).integers(1, 96, (1, t + 1), dtype=np.int32)
    want, _, _ = brumby_prefill(weights, jnp.asarray(ids), PCFG)
    _, state, _ = brumby_prefill(weights, jnp.asarray(ids[:, :t]), PCFG)
    pool = {n: jnp.stack([jnp.full_like(a, 3.0), a]) for n, a in
            state.items()}
    new, logits, stats = brumby_decode_step(
        weights, pool, jnp.asarray([5, ids[0, t]]),
        jnp.array([2, t], jnp.int32), jnp.asarray([False, True]), PCFG)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(want[0, t]),
                               rtol=2e-4, atol=2e-4)
    for n in pool:
        assert float(jnp.min(new[n][0])) == float(jnp.max(new[n][0])) == 3.0
    # the statistics count the active slot alone
    assert float(stats["retention"][1]) == pytest.approx(
        float(jnp.max(jnp.abs(new["S"][1]))))


def test_grouped_heads_and_half_split_rotation(model):
    """40 over 8 in small: query head ``a`` reads key/value head ``a // 2``;
    the rotation pairs ``x[i]`` with ``x[i + 8]`` and is the reference's;
    the interleaved pairing (the other served models') is another."""
    cfg, weights, _ = model
    a = weights["layers"][0]["attn"]
    h = jax.random.normal(jax.random.key(2), (5, 64))
    pos = jnp.arange(5) + 3
    q, k, v, g = retention_inputs(a, h, pos, PCFG)
    assert q.shape == (5, 4, 16) and k.shape == v.shape == (5, 2, 16)
    assert g.shape == (5, 2) and float(jnp.max(g)) < 0
    want = ref.rope_half(ref.rms_norm((h @ a["W_q"]).reshape(5, 4, 16),
                                      a["q_norm"], 1e-6), pos, 1e6)
    np.testing.assert_allclose(np.asarray(q), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    x = jax.random.normal(jax.random.key(9), (5, 4, 16))
    half = mla.rope(x, pos, 1e4, pairing="half")
    assert np.abs(np.asarray(half - mla.rope(x, pos, 1e4))).max() > 0.1
    np.testing.assert_allclose(
        np.asarray(jnp.sum(half * half, -1)), np.asarray(jnp.sum(x * x, -1)),
        rtol=1e-5)
    with pytest.raises(ValueError, match="pairing"):
        mla.rope(x, pos, 1e4, pairing="quarter")


# (c) the engine ------------------------------------------------------------


FAULTS = ("no_gate", "no_normaliser", "degree_1", "no_prompt_state",
          "wrong_group")


@pytest.mark.parametrize("prompt_len", [6, 8, 21],
                         ids=["short", "page-edge", "long"])
def test_engine_prefill_then_decode_through_the_state_pool_agrees_with_reference(
        model, prompt_len):
    """What the engine serves (prefill by the quadratic form, then decoding
    through the state pool) against the reference's quadratic form over the
    whole sequence, on logits: the served token's logit lies within float32
    rounding (1e-4: sums of a few hundred products of unit size) of the
    reference's best at every position; every planted fault lies outside
    it by 100x (the state kept in bfloat16 flips no token of nine over 96
    words: it is held to the logits below)."""
    cfg, weights, _ = model
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(1, cfg["vocab_size"], prompt_len, dtype=np.int32)
    res = _engine(weights).generate([prompt], max_new_tokens=9,
                                    eos_token=-1)[0]
    assert res.finish_reason == "length" and len(res.tokens) == 9
    sample = [{"prompt": prompt, "tokens": res.tokens}]
    got = ref.served_gaps(cfg, 5, sample, max_new=9, max_total=32,
                          weights=weights)
    assert got["tokens_read"] == 9
    assert got["served_logit_gap"] < 1e-4, got
    for fault in FAULTS:
        bad = ref.served_gaps(cfg, 5, sample, max_new=9, max_total=32,
                              weights=weights, control=fault)
        assert bad["control_logit_gap"] > 1e-2, (fault, bad)


@pytest.mark.parametrize("fault", FAULTS + ref.CONTROLS)
def test_each_planted_fault_moves_the_logits(model, fault):
    """Every fault and every control (the state kept in bfloat16 the least,
    0.055) moves a logit by 100 times the 2e-4 the program is held to."""
    cfg, weights, _ = model
    ids = np.random.default_rng(3).integers(1, 96, (1, 17), dtype=np.int32)
    at = np.arange(9, 17)[None]
    kw = dict(weights=weights, prompt_lens=np.array([9], np.int32))
    want = np.asarray(ref.logits_at(cfg, 5, ids, at, **kw))
    off = np.asarray(ref.logits_at(cfg, 5, ids, at, control=fault, **kw))
    assert np.abs(off - want).max() > 2e-2, fault


def test_ragged_prompts_reused_slots_and_inactive_slots(model):
    """Seven prompts of ragged lengths through three slots, read step by
    step: every slot is freed and taken again (its state replaced by the
    next prefill's), a step runs with slots empty, and each request's tokens
    are what it gets served alone."""
    cfg, weights, _ = model
    rng = np.random.default_rng(7)
    lens = [5, 24, 9, 1, 16, 12, 3]
    prompts = [rng.integers(1, 96, n, dtype=np.int32) for n in lens]
    new = [4, 9, 6, 11, 5, 8, 7]
    eng = _engine(weights)
    futs = [eng.submit(p, max_new_tokens=n, eos_token=-1)
            for p, n in zip(prompts, new)]
    fewest = 3
    while eng.scheduler.has_work():
        eng.step()
        eng.check_invariants()
        fewest = min(fewest, len(eng.scheduler.slots))
    assert fewest < 3                        # steps ran with a slot empty
    assert all(n == 0 for n in eng.cache.seq_lens)
    for p, n, fut in zip(prompts, new, futs):
        res = fut.result()
        assert res.finish_reason == "length" and len(res.tokens) == n
        alone = _engine(weights, max_slots=1).generate(
            [p], max_new_tokens=n, eos_token=-1)[0]
        np.testing.assert_array_equal(res.tokens, alone.tokens)
        got = ref.served_gaps(cfg, 5, [{"prompt": p, "tokens": res.tokens}],
                              max_new=11, max_total=40, weights=weights)
        assert got["served_logit_gap"] < 1e-4, got


def test_a_started_engine_serves_it_ahead_and_notes_its_statistics(model):
    """Through ``start()`` / ``submit`` (the step-ahead worker), in
    bfloat16: the spans carry the state's three statistics and the counters
    the two forms of the function."""
    cfg, _, narrow = model
    observe.reset()
    eng = _engine(narrow).start()
    try:
        rng = np.random.default_rng(11)
        futs = [eng.submit(rng.integers(1, 96, n, dtype=np.int32),
                           max_new_tokens=6, eos_token=-1)
                for n in (4, 13, 7, 20)]
        results = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    assert all(r.finish_reason == "length" and len(r.tokens) == 6
               for r in results)
    events = [e for e in observe.tracer().to_dict()["traceEvents"]
              if e.get("ph") == "X"]
    decodes = [e["args"] for e in events if e["name"] == "serving_decode"]
    prefills = [e["args"] for e in events if e["name"] == "serving_prefill"]
    assert decodes and len(prefills) == 4
    for args in decodes + prefills:
        assert args["ret_den_min"] > 0 and args["ret_state_absmax"] > 0
        assert 0.9 < args["ret_decay_mean"] < 1.0
    text = observe.metrics().render_prometheus()
    assert 'dl4j_tpu_retention_steps_total{form="quadratic"} 4' in text
    assert 'dl4j_tpu_retention_steps_total{form="state"}' in text
    assert "dl4j_tpu_retention_den_min" in text
    assert "dl4j_tpu_retention_state_absmax" in text


def test_what_a_state_model_is_refused(model):
    """No suffix prefill and no verify program: the radix prefix cache and
    speculation are refused at construction, with the reason; the engine
    names no model."""
    _, weights, _ = model
    m = BrumbyModel(PCFG, params=weights)
    with pytest.raises(ValueError, match="prefix_pages=4 needs a "
                       "suffix-prefill program and BrumbyModel has none"):
        GenerativeEngine(m, prefix_pages=4)
    with pytest.raises(ValueError, match="spec_k=2 needs a verify program"):
        GenerativeEngine(m, spec_k=2, draft_model=m)
    with pytest.raises(ValueError, match="retention"):
        BrumbyConfig.tiny(retention_degree=4)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    serving = os.path.join(here, "deeplearning4j_tpu", "serving")
    for name in os.listdir(serving):
        if name.endswith(".py"):
            with open(os.path.join(serving, name), encoding="utf-8") as f:
                assert "brumby" not in f.read().lower(), name


def test_the_context_limit_is_the_paged_arithmetic(model):
    """``page_size * max_pages_per_seq`` positions from the same two
    arguments, with no page behind them: a sequence that reaches it retires
    as ``overflow`` with what it has."""
    _, weights, _ = model
    eng = _engine(weights, max_pages_per_seq=4, max_prompt=8)
    assert eng.cache.max_context() == 16
    res = eng.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=20,
                       eos_token=-1)[0]
    assert res.finish_reason == "overflow" and 0 < len(res.tokens) < 20
    with pytest.raises(ValueError, match="exceeds per-slot context"):
        _engine(weights, max_pages_per_seq=2, max_prompt=8)


# (d) the pool --------------------------------------------------------------


def _pool(**kw):
    geo = SlotState(arrays={"S": ((2, 3, 4), "float32"),
                            "n": ((2,), "bfloat16")})
    args = dict(state=geo, page_size=4, max_slots=3, max_pages_per_seq=5)
    args.update(kw)
    return SlotStatePool(**args)


def test_the_pool_holds_each_array_in_its_own_dtype():
    pool = _pool()
    assert pool.kv["S"].shape == (3, 2, 3, 4)
    assert pool.kv["S"].dtype == jnp.float32
    assert pool.kv["n"].shape == (3, 2) and pool.kv["n"].dtype == jnp.bfloat16
    assert pool.max_context() == 20 and pool.pages_for(1000) == 0
    assert pool.free_pages == 0 == pool.num_pages
    pool.check_invariants()
    with pytest.raises(ValueError, match="at least one array"):
        SlotStatePool(state=SlotState(arrays={}))
    with pytest.raises(ValueError, match="positive"):
        _pool(page_size=0)


def test_the_pools_capacity_free_and_reset():
    pool = _pool()
    assert pool.ensure_capacity(1, 20) == "ok"
    assert pool.ensure_capacity(1, 21) == "overflow"
    pool.seq_lens[1] = 17
    assert pool.decode_args()[0].tolist() == [0, 17, 0]
    assert pool.decode_args()[0] is not pool.seq_lens
    assert pool.write_args(2, 9) == (2,)
    assert pool.free_slot(1) == 0 and pool.seq_lens[1] == 0
    faults.arm("page_oom", prob=1.0)
    try:
        assert pool.ensure_capacity(0, 1) == "oom"
    finally:
        faults.reset()
    pool.kv = {"S": None, "n": None}              # a crash took the buffers
    pool.reset_kv()
    pool.check_invariants()
    assert float(jnp.max(jnp.abs(pool.kv["S"]))) == 0.0


@pytest.mark.parametrize("breakage,message", [
    (lambda p: p.kv.pop("n"), "pool holds"),
    (lambda p: p.kv.update(S=p.kv["S"].astype(jnp.bfloat16)), "geometry says"),
    (lambda p: p.seq_lens.__setitem__(0, 21), "outside"),
    (lambda p: p.seq_lens.__setitem__(2, -1), "outside"),
], ids=["array-missing", "wrong-dtype", "past-the-context", "negative"])
def test_the_pools_invariants_catch(breakage, message):
    pool = _pool()
    breakage(pool)
    with pytest.raises(AssertionError, match=message):
        pool.check_invariants()


def test_the_state_programs_write_and_decode_in_place():
    """``write_prompt`` replaces exactly one slot's arrays (the reset of a
    reused slot); ``decode`` hands the model's ``decode_step`` the pool, the
    tokens, the lengths as positions and the active mask, and keeps an
    inactive slot's token."""
    pool = _pool()
    pool.kv = {n: jnp.ones_like(a) for n, a in pool.kv.items()}
    state = {"S": jnp.full((2, 3, 4), 5.0), "n": jnp.full((2,), 7.0)}
    new = build_state_write()(pool.kv, state, np.int32(2))
    assert float(jnp.min(new["S"][2])) == 5.0 == float(jnp.max(new["S"][2]))
    assert float(new["n"][2, 0]) == 7.0 and new["n"].dtype == jnp.bfloat16
    assert float(jnp.max(new["S"][:2])) == 1.0 == float(jnp.min(new["S"][:2]))

    def decode_step(params, kv, tokens, positions, active):
        logits = jax.nn.one_hot(tokens + positions + 1, 50) * active[:, None]
        return {n: a + 1 for n, a in kv.items()}, logits, {"x": positions}

    out, toks, _, stats = build_state_decode(decode_step)(
        None, new, np.array([3, 0, 4], np.int32),
        np.array([10, 20, 30], np.int32), np.array([1, 0, 1], np.int32),
        jax.random.key(0), np.zeros(3, np.float32), np.zeros(3, np.int32),
        np.ones(3, np.float32))
    assert toks.tolist() == [14, 20, 35]
    assert float(out["S"][2, 0, 0, 0]) == 6.0
    assert stats["x"].tolist() == [3, 0, 4]
