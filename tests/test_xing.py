"""Xing4.0 through the normal serving path, against the benchmark's plain
reference (benchmarks/reference/xing.py): float32, tiny sizes that keep every
mechanism — one dense and two expert layers, four hyper-connected streams
with 20 Sinkhorn iterations, 16 sigmoid-routed experts top-4 and a shared
one, MLA with YaRN."""

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

from reference import xing as ref  # noqa: E402

from deeplearning4j_tpu import observe  # noqa: E402
from deeplearning4j_tpu.models import mla  # noqa: E402
from deeplearning4j_tpu.models.xing import (  # noqa: E402
    XingConfig, XingModel, hyper_maps, hyper_sublayer, xing_cache_rows,
    xing_decode_step, xing_prefill, _experts)
from deeplearning4j_tpu.parallel.moe import moe_topk_share  # noqa: E402
from deeplearning4j_tpu.serving import GenerativeEngine  # noqa: E402

PAGE = 4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def bench_cfg(**kw):
    """The tiny model as a benchmark configuration (the reference's view)."""
    cfg = dict(
        vocab_size=96, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=24, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=16,
        q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
        v_head_dim=12, n_routed_experts=16, n_shared_experts=1,
        num_experts_per_tok=4, routed_scaling_factor=2, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=dict(YARN),
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
        param_dtype="float32",
        init={"embed_sigma": 0.5, "router": 1.5, "W_o": 3.0,
              "hc_b_sigma": 0.5, "hc_b_pre": -1.1, "hc_b_res_diag": 2.0})
    cfg.update(kw)
    return cfg


PCFG = XingConfig.tiny()
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
    return GenerativeEngine(XingModel(PCFG, params=weights), **geo)


# (a) ---------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len", [6, 8, 21],
                         ids=["inside-a-page", "page-edge", "several-pages"])
def test_engine_prefill_then_paged_decode_agrees_with_reference(
        model, prompt_len):
    """What the engine serves (prefill, then decoding through the latent
    paged cache) against the reference's full forward pass, on logits: the
    served token's logit lies within float32 rounding (1e-4: sums of a few
    hundred products of unit size) of the reference's best at every
    position; every planted fault lies outside it at some prompt."""
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
    for fault in ("no_renorm", "no_shared_expert", "no_yarn"):
        bad = ref.served_gaps(cfg, 5, sample, max_new=9, max_total=32,
                              weights=weights, control=fault)
        assert bad["control_logit_gap"] > 1e-2, (fault, bad)


def test_prefill_logits_equal_the_reference_and_bfloat16_does_not(model):
    """Tolerance 2e-4: float32 sums in two orders over logits of unit size.
    The same program over the same values in bfloat16 misses it by 100x, and
    so does each fault of the residual path."""
    cfg, weights, narrow = model
    ids = np.random.default_rng(0).integers(1, 96, (1, 13), dtype=np.int32)
    at = np.arange(13)[None]
    logits, rows, stats = xing_prefill(weights, jnp.asarray(ids), PCFG)
    want = np.asarray(ref.logits_at(cfg, 5, ids, at, weights=weights))
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-4, atol=2e-4)
    assert logits.dtype == jnp.float32
    assert rows.shape == (3, 1, 1, 13, 128) == (
        3, 1, 1, 13, xing_cache_rows(PCFG).width)
    assert stats["moe"].shape == (2, 16 + 2)      # the dense layer gives none
    assert int(stats["moe"].sum()) == 2 * 13 * 4
    low, _, _ = xing_prefill(narrow, jnp.asarray(ids), PCFG)
    assert np.abs(np.asarray(low) - want).max() > 2e-2
    for fault in ("no_sinkhorn", "static_hc"):
        off = np.asarray(ref.logits_at(cfg, 5, ids, at, weights=weights,
                                       control=fault))
        assert np.abs(off - want).max() > 2e-2, fault


# (b) ---------------------------------------------------------------------


def test_absorbed_decode_equals_materialised_attention(model):
    """One decode step over a cache written by the prefill gives the logits
    the prefill gives for the same token at the same position, four streams
    and all."""
    cfg, weights, _ = model
    t = 11
    ids = np.random.default_rng(1).integers(1, 96, (1, t + 1), dtype=np.int32)
    want, _, _ = xing_prefill(weights, jnp.asarray(ids), PCFG)
    _, rows, _ = xing_prefill(weights, jnp.asarray(ids[:, :t]), PCFG)
    n_pages, width = 4, xing_cache_rows(PCFG).width
    pool = np.zeros((3, 1, n_pages + 1, PAGE, width), np.float32)
    table = np.array([[2, 0, 3, 1]], np.int32)
    flat = np.asarray(rows)[:, 0, 0]                      # (3, t, W)
    for p in range(t):
        pool[:, 0, table[0, p // PAGE], p % PAGE] = flat[:, p]
    _, logits, stats = xing_decode_step(
        weights, jnp.asarray(pool), jnp.asarray(ids[0, t:]),
        jnp.array([t], jnp.int32), jnp.asarray(table),
        jnp.array([t + 1], jnp.int32),
        jnp.array([table[0, t // PAGE]], jnp.int32),
        jnp.array([t % PAGE], jnp.int32), PCFG)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[0, t]),
                               rtol=2e-4, atol=2e-4)
    assert int(np.asarray(stats["moe"]).sum()) == 2 * 4   # one token, top-4


# (c) the residual path -----------------------------------------------------


def _numpy_sinkhorn(r, iters=20, eps=1e-6):
    m = np.exp(np.clip(r.astype(np.float64), -30, 30))
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


@pytest.mark.parametrize("spread,tol", [(0.25, 1e-4), (4.0, None)],
                         ids=["moderate-logits", "wide-logits"])
def test_h_res_is_doubly_stochastic_and_the_residual_is_numpys(spread, tol):
    """After 20 iterations ``H_res``'s rows and columns sum to 1: to 1e-4
    where the logits are moderate (standard deviation 0.25 around a diagonal
    of 2); wide logits converge more slowly (the tail is heavy: at a standard
    deviation of 1 the median token is at ``hc_eps`` and one in 2000 at
    1e-2), and what is left is exactly what a numpy Sinkhorn in float64
    leaves: ``hc_residual`` measures the iteration, not the arithmetic."""
    n, d, t = 4, 32, 40
    ks = jax.random.split(jax.random.key(7), 3)
    hc = {"phi": spread * jax.random.normal(ks[0], (n * d, 24)) / (n * d)**.5,
          "b": jnp.concatenate([jnp.zeros(8), 2.0 * jnp.eye(n).reshape(-1)]),
          "a": jnp.ones(3)}
    x = jax.random.normal(ks[1], (t, n, d))
    valid = jnp.arange(t) < 33
    h_pre, h_post, h_res, (residual, clamped) = hyper_maps(hc, x, PCFG, valid)
    assert h_pre.shape == (n, t) and h_res.shape == (n, n, t)
    flat = np.asarray(x).reshape(t, n * d)
    xh = flat / np.sqrt((flat**2).mean(-1, keepdims=True) + 1e-6)
    z = xh @ np.asarray(hc["phi"])
    want = _numpy_sinkhorn(z[:, 8:].reshape(t, n, n) + 2.0 * np.eye(n))
    np.testing.assert_allclose(np.asarray(h_res).transpose(2, 0, 1), want,
                               rtol=1e-4, atol=1e-6)
    off = np.maximum(np.abs(want.sum(-1) - 1), np.abs(want.sum(-2) - 1))
    np.testing.assert_allclose(float(residual), off[:33].max(),
                               rtol=0.05, atol=2e-6)
    if tol is not None:
        assert float(residual) < tol
        assert np.abs(np.asarray(h_res).sum(0) - 1).max() < tol
        assert np.abs(np.asarray(h_res).sum(1) - 1).max() < tol
    assert int(clamped) == 0
    # the reference's maps are the same maps
    r_pre, r_post, r_res = ref.hyper_maps(hc, x, ST)
    np.testing.assert_allclose(np.asarray(h_pre).T, np.asarray(r_pre),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_post).T, np.asarray(r_post),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_res).transpose(2, 0, 1),
                               np.asarray(r_res), rtol=1e-4, atol=1e-6)


def test_the_clamp_is_on_the_logits_and_counted():
    """``a_res`` of 100 sends logits past +-30: they are clamped before the
    ``exp`` (nothing overflows), counted over the valid tokens only, and the
    iteration still normalises the rows."""
    n, d, t = 4, 32, 6
    ks = jax.random.split(jax.random.key(8), 2)
    hc = {"phi": jax.random.normal(ks[0], (n * d, 24)) / (n * d) ** .5,
          "b": jnp.zeros(24), "a": jnp.array([1.0, 1.0, 100.0])}
    x = jax.random.normal(ks[1], (t, n, d))
    valid = jnp.arange(t) < 4
    _, _, h_res, (residual, clamped) = hyper_maps(hc, x, PCFG, valid)
    flat = np.asarray(x).reshape(t, n * d)
    z = (flat / np.sqrt((flat**2).mean(-1, keepdims=True) + 1e-6)
         ) @ np.asarray(hc["phi"])
    raw = 100.0 * z[:4, 8:]
    assert int(clamped) == int((np.abs(raw) >= 30).sum()) > 0
    assert np.isfinite(np.asarray(h_res)).all()
    assert np.abs(np.asarray(h_res).sum(1) - 1).max() < 1e-4   # rows: last
    assert np.isfinite(float(residual))


def test_planted_maps_collapse_to_the_plain_pre_norm_residual():
    """Equal streams with ``H_pre = 1/n``, ``H_post = 1``, ``H_res = I``
    (planted through the parameters: ``phi`` nought, ``b_pre = -ln 3``,
    ``b_post = 0``, ``b_res = +-30``) are the plain residual layer: every
    stream becomes ``x + F(x)``."""
    n, d, t = 4, 32, 9
    b_res = np.where(np.eye(n) > 0, 30.0, -30.0).reshape(-1)
    hc = {"phi": jnp.zeros((n * d, 24)),
          "b": jnp.asarray(np.concatenate([np.full(n, -np.log(3.0)),
                                           np.zeros(n), b_res]), jnp.float32),
          "a": jnp.ones(3)}
    x = jax.random.normal(jax.random.key(2), (t, d))
    w = jax.random.normal(jax.random.key(3), (d, d)) / d ** .5
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.key(4), (d,))

    def fn(u):
        return jnp.tanh(mla.rms(u, gain, 1e-6) @ w), None

    streams = jnp.broadcast_to(x[:, None, :], (t, n, d))
    out, _, (residual, clamped) = hyper_sublayer(hc, streams, fn, PCFG)
    want = x + fn(x)[0]
    for i in range(n):
        np.testing.assert_allclose(np.asarray(out[:, i]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert float(residual) < 1e-5 and int(clamped) == t * n * n


# (d) the router ------------------------------------------------------------


def _moe_kw(**kw):
    base = dict(top_k=4, n_routed=16, n_zero=0, scale=2.0, held=(0, 16),
                score="sigmoid", renormalise=True)
    base.update(kw)
    return base


def test_sigmoid_router_bias_moves_the_choice_not_the_weight(model):
    cfg, weights, _ = model
    m = dict(weights["layers"][1]["moe"])
    u = jax.random.normal(jax.random.key(4), (29, 32), jnp.float32)
    # a bias that sends EVERY token to expert 5 (and three others): nothing
    # is dropped under the imbalance
    m["bias"] = jnp.zeros((16,)).at[5].set(10.0)
    y, stats = moe_topk_share(m, u, bias=m["bias"], **_moe_kw())
    assert int(stats[5]) == 29 and int(stats[:16].sum()) == 29 * 4
    assert int(stats[-2]) == 0 and int(stats[-1]) == 0   # no zero, no absent
    want = ref.moe(m, u, ST, lambda x: x, "no_shared_expert")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the weights are the sigmoids of the chosen, renormalised to the
    # scaling factor, whatever the bias
    chosen, weight = ref.route(m, u, ST)
    assert bool((chosen == 5).any(axis=-1).all())
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.0, rtol=1e-6)
    s = jax.nn.sigmoid(u @ m["router"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    np.testing.assert_allclose(
        np.asarray(weight), np.asarray(2.0 * picked / picked.sum(-1,
                                                                 keepdims=True)),
        rtol=1e-6)
    # expert 5 alone: its term carries that weight
    only5 = {k: (m[k][5:6] if k in ("Wg", "Wu", "Wd") else m[k]) for k in m}
    y5, _ = moe_topk_share(only5, u, bias=m["bias"], **_moe_kw(held=(5, 1)))
    w5 = jnp.sum(jnp.where(chosen == 5, weight, 0.0), axis=-1)
    e5 = ref.swiglu(u, m["Wg"][5], m["Wu"][5], m["Wd"][5], lambda x: x)
    np.testing.assert_allclose(np.asarray(y5), np.asarray(w5[:, None] * e5),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("renorm", [True, False])
def test_the_renormalisation_is_an_argument_of_the_one_layer(model, renorm):
    cfg, weights, _ = model
    m = weights["layers"][2]["moe"]
    u = jax.random.normal(jax.random.key(6), (17, 32), jnp.float32)
    y, _ = moe_topk_share(m, u, bias=m["bias"],
                          **_moe_kw(renormalise=renorm))
    want = ref.moe(m, u, ST, lambda x: x, None if renorm else "no_renorm",
                   experts=(0, 16))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        moe_topk_share(m, u, **_moe_kw(score="tanh"))


def test_the_shares_add_up(model):
    """The 16 experts split into 8 held shares of 2, with the shared expert
    counted once, equal the uncut layer: the model's and the reference's."""
    cfg, weights, _ = model
    m = weights["layers"][1]["moe"]
    u = jax.random.normal(jax.random.key(3), (17, 32), jnp.float32)
    want = ref.moe(m, u, ST, lambda x: x, None)
    total = mla.swiglu(m["shared"], u)                      # once
    picks = 0
    for first in range(0, 16, 2):
        share = {k: (m[k][first:first + 2] if k in ("Wg", "Wu", "Wd")
                     else m[k]) for k in m}
        y, stats = moe_topk_share(share, u, bias=m["bias"],
                                  **_moe_kw(held=(first, 2)))
        total = total + y
        picks += int(stats[:2].sum())
        assert int(stats.sum()) == 17 * 4      # held + absent: every pick
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert picks == 17 * 4      # every pick is held by exactly one share
    whole, stats = _experts(m, u, PCFG, None, jnp.float32)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(stats[:16].sum()) == 17 * 4


# (e) YaRN -------------------------------------------------------------------


def test_yarn_frequencies_and_scale_are_the_references():
    """At the published sizes (64 rotary values, theta 10000, factor 64 over
    4096 positions): pairs 0-9 keep their frequency, pairs 23-31 have it
    divided by 64, a ramp between; the softmax scale is 192^-0.5 * (0.1 ln 64
    + 1)^2. LongCat's attention has no YaRN and keeps the plain ones."""
    cfg = XingConfig()
    yarn = cfg.rope_scaling
    assert yarn == mla.Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert XingConfig(rope_scaling=dict(YARN)).rope_scaling == yarn
    got = np.asarray(mla.inv_freq(64, 10000.0, yarn))
    st = ref._static(dict(bench_cfg(), qk_rope_head_dim=64,
                          qk_nope_head_dim=128))
    np.testing.assert_allclose(got, np.asarray(
        ref.inv_freq(64, 10000.0, st.yarn)), rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    assert (got[11:23] < plain[11:23]).all()
    assert (got[11:23] > plain[11:23] / 64).all()
    np.testing.assert_allclose(np.asarray(mla.inv_freq(64, 10000.0)), plain,
                               rtol=1e-6)
    want = 192 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2
    assert cfg.mla.softmax_scale == pytest.approx(want, rel=1e-12)
    assert ref.softmax_scale(st) == pytest.approx(want, rel=1e-12)
    assert ref.softmax_scale(st, "no_yarn") == pytest.approx(192 ** -0.5)
    # the rotation itself against the reference's, at positions past 4096
    x = jax.random.normal(jax.random.key(0), (5, 3, 64))
    pos = jnp.array([0, 1, 4095, 4096, 100000])
    np.testing.assert_allclose(
        np.asarray(mla.rope(x, pos, 10000.0, yarn)),
        np.asarray(ref.rope(x, pos, 10000.0, st.yarn)), rtol=1e-5, atol=1e-5)
    from deeplearning4j_tpu.models.longcat import LongcatConfig
    assert LongcatConfig().mla.yarn is None
    assert LongcatConfig().mla.softmax_scale == 1.0 / np.sqrt(192.0)


# (f), (g) -------------------------------------------------------------------


def test_counters_span_arguments_and_compile_once(model):
    cfg, weights, _ = model
    observe.reset()
    eng = _engine(weights)
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (5, 12, 9, 17)]
    res = eng.generate(prompts, max_new_tokens=7, eos_token=-1)
    assert all(r.finish_reason == "length" for r in res)
    snap = observe.metrics().snapshot()
    kinds = {k: snap[f'dl4j_tpu_moe_picks_total{{kind="{k}"}}']["value"]
             for k in ("held", "zero", "absent")}
    # every prompt token once and every decoded token but the last of each,
    # top-4 in each of the two expert layers; nothing zero, nothing absent
    tokens = sum(len(p) for p in prompts) + 4 * (7 - 1)
    assert kinds == {"held": tokens * 4 * 2, "zero": 0, "absent": 0}
    assert any('expert="0"' in k for k in snap
               if k.startswith("dl4j_tpu_moe_expert_tokens_total"))
    steps = [e for e in observe.tracer().to_dict()["traceEvents"]
             if e["name"] in ("serving_decode", "serving_prefill")]
    assert {e["name"] for e in steps} == {"serving_decode", "serving_prefill"}
    assert all({"moe_held", "moe_zero", "moe_absent", "moe_max_over_mean",
                "hc_residual", "hc_clamped"} <= set(e["args"]) for e in steps)
    assert all(0 <= e["args"]["hc_residual"] < 0.1 for e in steps)
    # every expert is held, so no program has a head to fit: all rows, in
    # one chunk at these sizes (a decode step: 3 slots x 4 picks)
    assert all(e["args"]["moe_path"] == "all" for e in steps)
    assert {e["args"]["moe_tile"] for e in steps
            if e["name"] == "serving_decode"} == {12}
    assert sum(v["value"] for k, v in snap.items() if k.startswith(
        "dl4j_tpu_moe_grouped_steps_total")) == len(steps)
    assert snap["dl4j_tpu_hc_sinkhorn_residual"]["count"] == len(steps)
    assert snap["dl4j_tpu_hc_clamped_total"]["value"] == sum(
        e["args"]["hc_clamped"] for e in steps) == 0
    events = observe.ledger().events()
    assert not [e for e in events if e.cause == "new_shape"], events
    first = [e.key for e in events if e.cause == "first_compile"
             and e.graph == "serving"]
    assert sorted(first) == ["decode", "prefill", "write_prompt"]


@pytest.mark.parametrize("option", [{"prefix_pages": 4},
                                    {"spec_k": 2, "draft_model": object()}])
def test_engine_refuses_what_the_model_has_no_program_for(model, option):
    with pytest.raises(ValueError, match="XingModel has none"):
        _engine(model[1], **option)
