"""LongCat-Flash through the normal serving path, against the benchmark's
plain reference (benchmarks/reference/longcat.py): float32, tiny sizes that
keep every mechanism — 2 layers of two MLA sub-layers, 8 routed + 4 zero
experts, top-3, a share of the experts and the whole of them."""

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

from reference import longcat as ref  # noqa: E402

from deeplearning4j_tpu import observe  # noqa: E402
from deeplearning4j_tpu.models.longcat import (  # noqa: E402
    LongcatConfig, LongcatModel, cache_row_width, longcat_decode_step,
    longcat_prefill)
from deeplearning4j_tpu.parallel.moe import moe_topk_share  # noqa: E402
from deeplearning4j_tpu.serving import GenerativeEngine  # noqa: E402

PAGE = 4


def bench_cfg(held=(0, 8), **kw):
    """The tiny model as a benchmark configuration (the reference's view)."""
    cfg = dict(
        vocab_size=96, hidden_size=32, ffn_hidden_size=48,
        expert_ffn_hidden_size=24, num_layers=2, num_attention_heads=4,
        kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8,
        qk_nope_head_dim=8, v_head_dim=12, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, routed_scaling_factor=6,
        n_routed_experts=held[1], zero_expert_num=4, moe_topk=3,
        rms_norm_eps=1e-5, rope_theta=1e7, param_dtype="float32",
        published={"n_routed_experts": 8}, held_experts_first=held[0],
        init={"embed_sigma": 0.5, "router": 3.0})
    cfg.update(kw)
    return cfg


def program_cfg(cfg):
    return LongcatConfig.tiny(
        held_experts=ref.held(cfg), n_routed_experts=ref.routed_total(cfg))


def share_of(weights, held):
    """The same model with only ``held`` of its experts (a rank's weights)."""
    first, count = held
    out = dict(weights, layers=[])
    for lp in weights["layers"]:
        moe = dict(lp["moe"])
        for k in ("Wg", "Wu", "Wd"):
            moe[k] = moe[k][first:first + count]
        out["layers"].append(dict(lp, moe=moe))
    return out


@pytest.fixture(scope="module")
def whole():
    cfg = bench_cfg()
    return cfg, ref.make_weights(cfg, 5, jnp.float32)


@pytest.fixture(scope="module")
def shared():
    """Rank 1 of 2: experts 4..7 of 8."""
    cfg = bench_cfg(held=(4, 4))
    return cfg, share_of(ref.make_weights(bench_cfg(), 5, jnp.float32), (4, 4))


def _engine(cfg, weights, **kw):
    geo = dict(max_slots=3, page_size=PAGE, max_pages_per_seq=10,
               max_prompt=24)
    geo.update(kw)
    return GenerativeEngine(LongcatModel(program_cfg(cfg), params=weights),
                            **geo)


# (a) ---------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len", [6, 8, 21],
                         ids=["inside-a-page", "page-edge", "several-pages"])
@pytest.mark.parametrize("which", ["whole", "shared"])
def test_engine_prefill_then_paged_decode_agrees_with_reference(
        request, which, prompt_len):
    """What the engine serves (prefill, then decoding through the latent
    paged cache) against the reference's full forward pass, on logits: the
    served token's logit lies within rounding of the reference's best at
    every position."""
    cfg, weights = request.getfixturevalue(which)
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(1, cfg["vocab_size"], prompt_len, dtype=np.int32)
    eng = _engine(cfg, weights)
    res = eng.generate([prompt], max_new_tokens=9, eos_token=-1)[0]
    assert res.finish_reason == "length" and len(res.tokens) == 9
    got = ref.served_gaps(cfg, 5, [{"prompt": prompt, "tokens": res.tokens}],
                          max_new=9, max_total=32, weights=weights)
    assert got["tokens_read"] == 9
    assert got["served_logit_gap"] < 1e-4, got
    # and a fault the comparison has to see: the zero experts left out
    bad = ref.served_gaps(cfg, 5, [{"prompt": prompt, "tokens": res.tokens}],
                          max_new=9, max_total=32, weights=weights,
                          control="no_zero_experts")
    assert bad["control_logit_gap"] > 1e-3, bad


def test_prefill_logits_equal_the_reference(whole):
    cfg, weights = whole
    ids = np.random.default_rng(0).integers(1, 96, (1, 13), dtype=np.int32)
    logits, rows, stats = longcat_prefill(weights, jnp.asarray(ids),
                                          program_cfg(cfg))
    want = ref.logits_at(cfg, 5, ids, np.arange(13)[None], weights=weights)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert rows.shape == (4, 1, 1, 13, cache_row_width(program_cfg(cfg)))
    assert stats.shape == (2, 8 + 2)


# (b) ---------------------------------------------------------------------


def test_absorbed_decode_equals_materialised_attention(whole):
    """One decode step over a cache written by the prefill gives the logits
    the prefill gives for the same token at the same position."""
    cfg, weights = whole
    pcfg = program_cfg(cfg)
    t = 11
    ids = np.random.default_rng(1).integers(1, 96, (1, t + 1), dtype=np.int32)
    want, _, _ = longcat_prefill(weights, jnp.asarray(ids), pcfg)
    _, rows, _ = longcat_prefill(weights, jnp.asarray(ids[:, :t]), pcfg)
    n_pages = 4
    width = cache_row_width(pcfg)
    pool = np.zeros((4, 1, n_pages + 1, PAGE, width), np.float32)
    table = np.array([[2, 0, 3, 1]], np.int32)
    flat = np.asarray(rows)[:, 0, 0]                      # (4, t, W)
    for p in range(t):
        pool[:, 0, table[0, p // PAGE], p % PAGE] = flat[:, p]
    pool_out, logits, stats = longcat_decode_step(
        weights, jnp.asarray(pool), jnp.asarray(ids[0, t:]),
        jnp.array([t], jnp.int32), jnp.asarray(table),
        jnp.array([t + 1], jnp.int32),
        jnp.array([table[0, t // PAGE]], jnp.int32),
        jnp.array([t % PAGE], jnp.int32), pcfg)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[0, t]),
                               rtol=2e-4, atol=2e-4)
    assert int(np.asarray(stats).sum()) == 2 * 3   # one token, top-3, 2 layers


def test_latent_op_kernel_equals_generic():
    """The registry's op: the generic gather against a numpy oracle, and the
    Pallas kernel (interpreted) against both."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        _check_latent_decode_attention)
    from deeplearning4j_tpu.ops.registry import registry

    assert "latent_decode_attention" in registry()
    _check_latent_decode_attention()


# (c) ---------------------------------------------------------------------


def test_the_shares_add_up(whole):
    """The held-expert terms of all shares plus the zero-expert term counted
    once equal the uncut reference's expert layer."""
    cfg, weights = whole
    m = weights["layers"][0]["moe"]
    u = jax.random.normal(jax.random.key(3), (17, 32), jnp.float32)
    want = ref.moe(m, u, ref._static(cfg), lambda x: x, None)
    kw = dict(top_k=3, n_routed=8, n_zero=4, scale=6.0, bias=m["bias"])
    zero_only, stats0 = moe_topk_share(m, u, held=(0, 0), **kw)
    total = zero_only
    picks = int(stats0[-2])
    for first, count in ((0, 3), (3, 2), (5, 3)):
        share = {k: (m[k][first:first + count] if k in ("Wg", "Wu", "Wd")
                     else m[k]) for k in m}
        y, stats = moe_topk_share(share, u, held=(first, count), **kw)
        total = total + (y - zero_only)
        picks += int(stats[:count].sum())
        assert int(stats[-2]) == int(stats0[-2])
        assert int(stats.sum()) == 17 * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert picks == 17 * 3      # every pick is held by exactly one share


# (d) ---------------------------------------------------------------------


def test_bias_moves_the_choice_not_the_weight_and_nothing_is_dropped(whole):
    cfg, weights = whole
    m = dict(weights["layers"][1]["moe"])
    u = jax.random.normal(jax.random.key(4), (29, 32), jnp.float32)
    kw = dict(top_k=3, n_routed=8, n_zero=4, scale=6.0, held=(0, 8))
    # a bias that sends EVERY token to expert 5 (and two others): none dropped
    m["bias"] = jnp.zeros((12,)).at[5].set(10.0)
    y, stats = moe_topk_share(m, u, bias=m["bias"], **kw)
    assert int(stats[5]) == 29 and int(stats.sum()) == 29 * 3
    want = ref.moe(m, u, ref._static(cfg), lambda x: x, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the weight of expert 5 is scale * softmax, whatever the bias
    s = jax.nn.softmax(u @ m["router"], axis=-1)
    only5 = {k: (m[k][5:6] if k in ("Wg", "Wu", "Wd") else m[k]) for k in m}
    y5, _ = moe_topk_share(only5, u, bias=m["bias"],
                           **dict(kw, held=(5, 1)))
    z, _ = moe_topk_share(only5, u, bias=m["bias"], **dict(kw, held=(0, 0)))
    e5 = ref.swiglu(u, m["Wg"][5], m["Wu"][5], m["Wd"][5], lambda x: x)
    np.testing.assert_allclose(np.asarray(y5 - z),
                               np.asarray(6.0 * s[:, 5:6] * e5),
                               rtol=1e-5, atol=1e-5)


def test_grouped_product_fits_the_held_rows_or_takes_them_all():
    """A rank that holds 4 of 256 routed experts gives the grouped product
    128 of a batch's 768 (token, pick) rows; a router that sends every token
    to a held expert overflows them, and the layer then takes all the rows:
    both ways the reference's sum, no token dropped."""
    d, w, t, n_routed, n_zero, top_k = 32, 24, 256, 256, 128, 3
    held = (8, 4)
    ks = jax.random.split(jax.random.key(9), 5)
    m = {"router": 3.0 * jax.random.normal(ks[0], (d, n_routed + n_zero)) / d**0.5,
         "bias": jnp.zeros((n_routed + n_zero,)),
         "Wg": jax.random.normal(ks[1], (4, d, w)) / d**0.5,
         "Wu": jax.random.normal(ks[2], (4, d, w)) / d**0.5,
         "Wd": jax.random.normal(ks[3], (4, w, d)) / w**0.5}
    u = jax.random.normal(ks[4], (t, d), jnp.float32)
    st = ref._static(bench_cfg(
        held=held, hidden_size=d, zero_expert_num=n_zero, moe_topk=top_k,
        published={"n_routed_experts": n_routed}))
    kw = dict(top_k=top_k, n_routed=n_routed, n_zero=n_zero, scale=6.0,
              held=held)
    for bias, rows in ((m["bias"], "fit"),
                       (m["bias"].at[9].set(10.0), "overflow")):
        mb = dict(m, bias=bias)
        y, stats = moe_topk_share(mb, u, bias=bias, **kw)
        held_rows = int(stats[:4].sum())
        assert (held_rows <= 128) == (rows == "fit"), (rows, held_rows)
        assert int(stats.sum()) == t * top_k
        want = ref.moe(mb, u, st, lambda x: x, None)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# (e), (f) ------------------------------------------------------------------


def test_counters_and_compile_once(shared):
    cfg, weights = shared
    observe.reset()
    eng = _engine(cfg, weights)
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (5, 12, 9, 17)]
    res = eng.generate(prompts, max_new_tokens=7, eos_token=-1)
    assert all(r.finish_reason == "length" for r in res)
    snap = observe.metrics().snapshot()
    kinds = {k: snap[f'dl4j_tpu_moe_picks_total{{kind="{k}"}}']["value"]
             for k in ("held", "zero", "absent")}
    # every prompt token once and every decoded token but the last of each
    tokens = sum(len(p) for p in prompts) + 4 * (7 - 1)
    assert sum(kinds.values()) == tokens * 3 * 2
    per_expert = sum(v["value"] for k, v in snap.items()
                     if k.startswith("dl4j_tpu_moe_expert_tokens_total"))
    assert per_expert == kinds["held"]
    assert all('expert="%d"' % e not in k for k in snap for e in range(4)
               if k.startswith("dl4j_tpu_moe_expert_tokens_total"))
    assert snap["dl4j_tpu_moe_load_max_over_mean"]["count"] >= 6
    spans = [e for e in observe.tracer().to_dict()["traceEvents"]
             if e["name"] in ("serving_decode", "serving_prefill")]
    assert spans and all({"moe_held", "moe_zero", "moe_absent",
                          "moe_max_over_mean", "moe_path", "moe_tile"}
                         <= set(e["args"]) for e in spans)
    # how the grouped products engaged, a program: at these sizes every
    # (token, pick) row fits one chunk and there is no head to fit
    grouped = {k: v["value"] for k, v in snap.items()
               if k.startswith("dl4j_tpu_moe_grouped_steps_total")}
    assert sum(grouped.values()) == len(spans)
    assert all('path="all"' in k for k in grouped), grouped
    events = observe.ledger().events()
    assert not [e for e in events if e.cause == "new_shape"], events
    first = [e.key for e in events if e.cause == "first_compile"
             and e.graph == "serving"]
    assert sorted(first) == ["decode", "prefill", "write_prompt"]


@pytest.mark.parametrize("option", [{"prefix_pages": 4},
                                    {"spec_k": 2, "draft_model": object()}])
def test_engine_refuses_what_the_model_has_no_program_for(whole, option):
    cfg, weights = whole
    with pytest.raises(ValueError, match="LongcatModel has none"):
        _engine(cfg, weights, **option)


def test_sampling_stays_inside_the_held_vocabulary(shared):
    cfg, weights = shared
    eng = _engine(cfg, weights)
    res = eng.generate([np.arange(1, 10, dtype=np.int32)], max_new_tokens=12,
                       temperature=0.9, top_k=20, eos_token=-1)[0]
    assert len(res.tokens) == 12
    assert 0 <= int(res.tokens.min()) and int(res.tokens.max()) < 96
