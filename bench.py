"""Benchmark harness — prints ONE JSON line.

Headline metric (BASELINE.json): ResNet-50 ImageNet-shaped training
throughput, images/sec/chip. The reference published no numbers
(``BASELINE.json.published == {}``), so ``vs_baseline`` ratchets against the
last recorded value in BENCH_HISTORY.json (1.0 on first run).

A measurement needs the chip: the process must find a TPU, in-process, or
it exits non-zero with :class:`NoAcceleratorError` before any metric is
printed — there is no CPU fallback and no smoke size (``chip_smoke.py`` is
the proof that the main paths start on the chip; the CPU gates live in
``tools/``). One process owns the chip: this script starts no child.

Env knobs: BENCH_BATCH (default per model — 128 for resnet50, 4096 for
lenet), BENCH_ITERS (default 60 — the whole multi-step loop is ONE device
dispatch),
BENCH_MODEL (resnet50 | lenet), BENCH_IMAGE (default 224; resnet50 only —
LeNet is fixed 28×28 MNIST), BENCH_DTYPE (default "mixed": bf16 compute /
f32 params — the TPU-native policy; "float32" for the f32 baseline).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


class NoAcceleratorError(RuntimeError):
    """JAX found no TPU: nothing this script prints would be a measurement."""


def require_tpu():
    """The attached TPU device, found in THIS process (a probe child would
    hold the chip its parent then needs). Raises where there is none."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise NoAcceleratorError(
            f"a measurement needs a TPU and JAX found none: the default "
            f"platform is {dev.platform!r} ({dev.device_kind}). No metric "
            f"printed.")
    return dev


def _bench_resnet50(batch: int, iters: int, image: int, dtype: str):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import models, nn
    from deeplearning4j_tpu.datasets.image import synthetic_image_batch

    net = models.ResNet50(num_classes=1000, input_shape=(image, image, 3),
                          updater=nn.Nesterovs(learning_rate=0.1, momentum=0.9),
                          dtype=dtype).init()
    imgs, labels = synthetic_image_batch(batch, image, image, 3, 1000, seed=0)
    y = np.zeros((batch, 1000), np.float32)
    y[np.arange(batch), labels] = 1.0
    x = jnp.asarray(imgs)
    yj = jnp.asarray(y)

    # fused multi-step loop: lax.scan over the whole train step — zero host
    # dispatch between iterations (fit_scanned). Warm up with the SAME step
    # count so the timed call reuses the compiled executable.
    losses = net.fit_scanned(x, yj, steps=iters)
    assert np.isfinite(losses[-1])
    t0 = time.perf_counter()
    losses = net.fit_scanned(x, yj, steps=iters)
    dt = time.perf_counter() - t0
    assert np.isfinite(losses[-1])
    return batch * iters / dt, "resnet50_imagenet_train_images_per_sec"


def _bench_bert(batch: int, iters: int, dtype: str, seq: int):
    """BERT-base MLM train step, seq 512 — the attention-bound workload where
    the Pallas flash platform helper carries the win (BENCH_MODEL=bert)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.bert import BertConfig, BertModel
    # default dropout=0.1 — the production fine-tune config; the Pallas flash
    # helper handles attention-prob dropout IN-KERNEL since round 3, so the
    # fast path no longer needs dropout disabled
    cfg = BertConfig.base()
    model = BertModel(cfg, seed=0,
                      dtype=jnp.bfloat16 if dtype != "float32" else jnp.float32)
    rng = np.random.RandomState(0)
    batch_data = {
        "ids": rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
        "segments": np.zeros((batch, seq), np.int32),
        "mask": (rng.rand(batch, seq) > 0.1).astype(np.int32),
        "mlm_labels": rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
        "mlm_mask": (rng.rand(batch, seq) < 0.15).astype(np.float32),
    }
    losses = model.fit_mlm_scanned(batch_data, iters)  # compile + warmup
    assert np.isfinite(losses[-1])
    t0 = time.perf_counter()
    losses = model.fit_mlm_scanned(batch_data, iters)
    dt = time.perf_counter() - t0
    assert np.isfinite(losses[-1])
    return batch * seq * iters / dt, "bert_base_mlm_train_tokens_per_sec"


def _bench_lenet(batch: int, iters: int):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.datasets.mnist import synthetic_mnist

    net = models.LeNet(num_classes=10).init()
    feats, labels = synthetic_mnist(batch)
    y = np.zeros((batch, 10), np.float32)
    y[np.arange(batch), labels] = 1.0
    x = jnp.asarray(feats)
    yj = jnp.asarray(y)
    losses = net.fit_scanned(x, yj, steps=iters)
    assert np.isfinite(losses[-1])
    t0 = time.perf_counter()
    losses = net.fit_scanned(x, yj, steps=iters)
    dt = time.perf_counter() - t0
    assert np.isfinite(losses[-1])
    return batch * iters / dt, "lenet5_mnist_train_images_per_sec"


def _bench_attention(iters: int):
    """Flash-vs-generic attention at T=8192 d=64 bf16 fwd+bwd (the Pallas
    platform-helper headline; recorded as the BENCH_HISTORY 'attention'
    entry the kernel docstring points at). Device-side lax.scan loop: one
    dispatch per timed window."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention, _reference_attention)

    bh, t, d = 8, 8192, 64
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(bh, t, d).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(r.randn(bh, t, d).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(r.randn(bh, t, d).astype(np.float32)).astype(jnp.bfloat16)

    def make(loss_fn):
        grad = jax.grad(loss_fn, argnums=(0, 1, 2))

        @jax.jit
        def bench(q, k, v):
            def body(carry, _):
                dq, dk, dv = grad(carry, k, v)
                z = jnp.asarray(0.0, carry.dtype)
                return carry + z * dq + z * dk + z * dv, jnp.float32(0)

            qf, _ = jax.lax.scan(body, q, None, length=iters)
            return jnp.sum(qf.astype(jnp.float32))

        return bench

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, None, None, True,
                                       None, None, None, 0.0)
                       .astype(jnp.float32) ** 2)

    def gen_loss(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, scale=d ** -0.5,
                                            causal=True)
                       .astype(jnp.float32) ** 2)

    def run(bench):
        _ = float(bench(q, k, v))  # compile
        t0 = time.perf_counter()
        _ = float(bench(q, k, v))
        return (time.perf_counter() - t0) / iters

    t_flash = run(make(flash_loss))
    t_gen = run(make(gen_loss))
    return t_gen / t_flash, "flash_attention_t8192_speedup_vs_generic"


def _pct_ms(sorted_xs, q: float) -> float:
    """Nearest-rank percentile of an ascending latency list, in ms — ONE
    convention for every latency report this file emits."""
    return round(sorted_xs[min(len(sorted_xs) - 1,
                               int(q * len(sorted_xs)))] * 1e3, 3)


def _bench_serving(qps: float, n_requests: int, max_batch: int):
    """Serving-latency benchmark (BENCH_MODEL=serving): a fixed-QPS open
    load of ``ParallelInference.predict`` calls against a small MLP —
    requests are issued on schedule regardless of completions (open-loop,
    the honest way to measure tail latency under load; closed loops hide
    queueing). Value = achieved req/sec; the JSON line carries p50/p99 from
    the measured per-request latencies AND the observe/ snapshot carries
    the registry's serving histogram, so the bench trajectory records
    latency, not just throughput."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from deeplearning4j_tpu import nn
    from deeplearning4j_tpu.parallel.mesh import ParallelInference

    n_in, n_out = 32, 10
    conf = (nn.builder().seed(0).updater(nn.Adam(learning_rate=1e-3)).list()
            .layer(nn.DenseLayer(n_out=64, activation="relu"))
            .layer(nn.OutputLayer(n_out=n_out, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.feed_forward(n_in)).build())
    net = nn.MultiLayerNetwork(conf).init()
    pi = ParallelInference(net, max_batch=max_batch, window_ms=2.0).start()
    lat = [None] * n_requests
    try:
        pi.predict(np.zeros(n_in, np.float32))  # compile the serving path
        r = np.random.RandomState(0)
        reqs = r.randn(n_requests, n_in).astype(np.float32)

        def issue(i, t0):
            # t0 is the SUBMIT time: executor queueing counts toward the
            # client-perceived latency — starting the clock at worker
            # pickup would reintroduce coordinated omission exactly when
            # the pool saturates (the overload regime tails matter in)
            pi.predict(reqs[i])
            lat[i] = time.perf_counter() - t0

        futs = []
        with ThreadPoolExecutor(max_workers=32) as ex:
            t_start = time.perf_counter()
            for i in range(n_requests):
                delay = (t_start + i / qps) - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                futs.append(ex.submit(issue, i, time.perf_counter()))
        t_total = time.perf_counter() - t_start
        # a failed request must fail the bench, not silently shrink the
        # sample — survivors-only percentiles would record an inflated
        # watermark from a partially broken serving path
        errs = [f.exception() for f in futs if f.exception() is not None]
        if errs:
            raise RuntimeError(
                f"{len(errs)}/{n_requests} serving requests failed; "
                f"first: {errs[0]!r}")
    finally:
        pi.stop()
    done = sorted(l for l in lat if l is not None)
    assert done, "no serving request completed"
    extra = {"p50_ms": _pct_ms(done, 0.50), "p99_ms": _pct_ms(done, 0.99),
             "offered_qps": qps, "completed": len(done)}
    return len(done) / t_total, "serving_fixed_qps_req_per_sec", extra


def _bench_generate(qps: float, n_requests: int, gen_tokens: int,
                    max_slots: int, preset: str):
    """Generative-serving benchmark (BENCH_MODEL=generate): a fixed-QPS
    open-loop stream of text-generation requests against the continuous-
    batching engine (docs/SERVING.md) — submissions follow the schedule
    regardless of completions, same honesty argument as BENCH_MODEL=serving.
    Value = generated tokens/sec; the JSON line carries p50/p99
    time-to-first-token AND inter-token latency from the per-request
    measurements, plus the observe/ snapshot (admit/evict/generated
    counters, decode-step percentiles). The snapshot is PROCESS-WIDE and
    includes the warmup request's compile-inclusive latencies (same
    semantics as BENCH_MODEL=serving) — the steady-state percentiles are
    the top-level ttft_*/intertoken_* fields, measured post-warmup."""
    from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
    from deeplearning4j_tpu.serving import GenerativeEngine

    cfg = GptConfig.tiny(vocab_size=512) if preset == "tiny" else \
        GptConfig.base(vocab_size=8192, max_position=512)
    model = GptModel(cfg, seed=0)
    max_prompt = int(os.environ.get("BENCH_MAX_PROMPT", "16"))
    pages_per_seq = -(-(max_prompt + gen_tokens + 1) // 16) + 1
    eng = GenerativeEngine(model, max_slots=max_slots, page_size=16,
                           max_pages_per_seq=pages_per_seq,
                           max_prompt=max_prompt, seed=0).start()
    try:
        r = np.random.RandomState(0)
        prompts = [r.randint(1, cfg.vocab_size,
                             size=r.randint(2, max_prompt)).astype(np.int32)
                   for _ in range(n_requests)]
        # warm both compiled paths so the timed window measures serving,
        # not the first prefill/decode XLA compile
        eng.submit(prompts[0][:2], max_new_tokens=2,
                   eos_token=-1).result(timeout=600)
        futs = []
        t_start = time.perf_counter()
        for i in range(n_requests):
            delay = (t_start + i / qps) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futs.append(eng.submit(
                prompts[i], max_new_tokens=gen_tokens, temperature=0.8,
                top_k=40, top_p=0.95, eos_token=-1))
        results = [f.result(timeout=600) for f in futs]
        t_total = time.perf_counter() - t_start
    finally:
        eng.stop()
    n_tokens = sum(len(res.tokens) for res in results)
    assert n_tokens > 0, "no tokens generated"
    ttfts = sorted(res.ttft_s for res in results)
    itls = sorted(g for res in results for g in res.intertoken_s)
    extra = {"generated_tokens": n_tokens,
             "ttft_p50_ms": _pct_ms(ttfts, 0.50),
             "ttft_p99_ms": _pct_ms(ttfts, 0.99),
             "intertoken_p50_ms": _pct_ms(itls, 0.50) if itls else None,
             "intertoken_p99_ms": _pct_ms(itls, 0.99) if itls else None,
             "offered_qps": qps, "completed": len(results)}
    return n_tokens / t_total, "generate_open_loop_tokens_per_sec", extra


def _bench_generate_overload(n_requests: int, gen_tokens: int,
                             max_slots: int, factor: float,
                             slow_decode: bool):
    """Goodput-under-overload benchmark (BENCH_MODEL=generate +
    BENCH_OVERLOAD=1): the shared open-loop overload ramp
    (serving/overload.py, docs/SERVING.md § SLO admission frontend) run
    twice past measured capacity — SLOFrontend on, then raw engine.submit
    with the IDENTICAL offered schedule. Value = frontend-on goodput
    (completed-within-deadline tokens/sec, the ROADMAP 2(d) metric); the
    JSON line carries the frontend-off goodput, the ratio, shed/reason
    accounting and the ladder states visited, so "the frontend beats the
    baseline under overload" is a recorded number, not a claim. This is a
    POLICY benchmark, not a kernel benchmark: by default both legs arm
    the deterministic 50ms slow_decode service floor so the comparison
    measures admission policy rather than host scheduling jitter
    (BENCH_SLOW_DECODE=0 opts out for a raw-hardware ramp)."""
    from deeplearning4j_tpu.serving.overload import run_overload_ramp

    # throwaway warm-up: the first ramp in a process absorbs the slow
    # early XLA steps into its latency signal
    run_overload_ramp(frontend_on=False, n_requests=3,
                      gen_tokens=gen_tokens, max_slots=max_slots,
                      overload_factor=factor)
    on = run_overload_ramp(
        frontend_on=True, n_requests=n_requests, gen_tokens=gen_tokens,
        max_slots=max_slots, overload_factor=factor,
        slow_decode=slow_decode)
    off = run_overload_ramp(
        frontend_on=False, n_requests=n_requests, gen_tokens=gen_tokens,
        max_slots=max_slots, overload_factor=factor,
        slow_decode=slow_decode,
        capacity_tokens_per_sec=on["capacity_tokens_per_sec"])
    assert on["all_terminal"] and off["all_terminal"], \
        "overload ramp left non-terminal requests"
    g_on, g_off = on["goodput_tokens_per_sec"], off["goodput_tokens_per_sec"]
    extra = {
        "goodput_off": g_off,
        "goodput_ratio": round(g_on / g_off, 3) if g_off else None,
        "overload_factor": factor,
        "capacity_tokens_per_sec": on["capacity_tokens_per_sec"],
        "states_visited": on.get("states_visited"),
        "reasons_on": on["reasons"], "reasons_off": off["reasons"],
        "degraded_results": on["degraded_results"],
        "interactive_ttft_p99_ms_on": on.get("interactive_ttft_p99_ms"),
        "interactive_ttft_p99_ms_off": off.get("interactive_ttft_p99_ms"),
        "new_shape_events": on["new_shape_events"] + off["new_shape_events"],
    }
    return g_on, "generate_overload_goodput_tokens_per_sec", extra


def _bench_generate_prefix(n_requests: int, n_prefixes: int, sys_len: int,
                           gen_tokens: int):
    """Shared-prompt replay benchmark (BENCH_MODEL=generate +
    BENCH_PREFIX=1): the radix-prefix-cache acceptance harness
    (serving/replay.py, docs/SERVING.md § Radix prefix cache) run twice —
    cache on, then cache off with the IDENTICAL request plan. Value = the
    TTFT p50 speedup the cache buys (off/on); the JSON line carries both
    legs' TTFT percentiles, the hit accounting, and the bit-identical
    check, so "shared prompts admit in O(suffix)" is a recorded number.
    Both legs greedy: outputs MUST match token-for-token — a numerics
    regression in the suffix-prefill path fails the bench, not just a
    test."""
    from deeplearning4j_tpu.serving.replay import run_prefix_replay

    on = run_prefix_replay(prefix_on=True, n_requests=n_requests,
                           n_prefixes=n_prefixes, sys_len=sys_len,
                           gen_tokens=gen_tokens)
    off = run_prefix_replay(prefix_on=False, n_requests=n_requests,
                            n_prefixes=n_prefixes, sys_len=sys_len,
                            gen_tokens=gen_tokens)
    identical = on["outputs"] == off["outputs"]
    assert identical, (
        "prefix-cache outputs diverged from the cache-off oracle — the "
        "suffix-prefill path is numerically wrong")
    assert on["prefix_hit_tokens"] > 0, "replay produced zero prefix hits"
    speedup = (off["ttft_p50_ms"] / on["ttft_p50_ms"]
               if on["ttft_p50_ms"] else 0.0)
    extra = {
        "ttft_p50_ms_on": on["ttft_p50_ms"],
        "ttft_p50_ms_off": off["ttft_p50_ms"],
        "ttft_p99_ms_on": on["ttft_p99_ms"],
        "ttft_p99_ms_off": off["ttft_p99_ms"],
        "ttft_improvement_pct": round(100.0 * (1.0 - 1.0 / speedup), 1)
        if speedup else None,
        "prefix_hit_tokens": on["prefix_hit_tokens"],
        "hit_requests": on["hit_requests"],
        "requests": on["requests"],
        "outputs_identical": identical,
        "tree_pages": on.get("tree_pages"),
        "new_shape_events": on["new_shape_events"] + off["new_shape_events"],
    }
    return speedup, "generate_prefix_ttft_p50_speedup", extra


def _bench_generate_spec(n_requests: int, gen_tokens: int, spec_k: int):
    """Speculative-decoding benchmark (BENCH_MODEL=generate +
    BENCH_SPEC=1): the replay harness (serving/replay.py, docs/SERVING.md
    § Speculative decoding) run twice — spec on, then spec off with the
    IDENTICAL greedy request plan, both under the deterministic 50ms
    slow_decode target-step floor (the slo-gate measurement model). Value
    = the decode tokens/sec speedup speculation buys (on/off); the JSON
    line carries both legs' rates, the proposal/acceptance accounting,
    and the bit-identical check — losslessness fails the bench, not just
    a test."""
    from deeplearning4j_tpu.serving.replay import run_spec_replay

    on = run_spec_replay(spec_on=True, n_requests=n_requests,
                         gen_tokens=gen_tokens, spec_k=spec_k)
    off = run_spec_replay(spec_on=False, n_requests=n_requests,
                          gen_tokens=gen_tokens, spec_k=spec_k)
    identical = on["outputs"] == off["outputs"]
    assert identical, (
        "speculative outputs diverged from the spec-off oracle — the "
        "verify/rollback path is numerically wrong")
    assert on["accepted_tokens"] > 0, "replay accepted zero draft tokens"
    speedup = (on["tokens_per_sec"] / off["tokens_per_sec"]
               if off["tokens_per_sec"] else 0.0)
    extra = {
        "tokens_per_sec_on": on["tokens_per_sec"],
        "tokens_per_sec_off": off["tokens_per_sec"],
        "spec_k": on["spec_k"],
        "proposed_tokens": on["proposed_tokens"],
        "accepted_tokens": on["accepted_tokens"],
        "acceptance_rate": on["acceptance_rate"],
        "requests": on["requests"],
        "outputs_identical": identical,
        "first_compile_keys_on": on["first_compile_keys"],
        "new_shape_events": on["new_shape_events"] + off["new_shape_events"],
    }
    return speedup, "generate_spec_tokens_per_sec_speedup", extra


def _bench_generate_random_shapes(n_requests: int, gen_max: int,
                                  spec_k: int):
    """Shape-diversity benchmark (BENCH_MODEL=generate +
    BENCH_RANDOM_SHAPES=1): the graftshape cross-validation workload
    (serving/replay.py, docs/LINT.md § graftshape) — prompt lengths
    drawn across the whole 1..max_prompt range, varied generation
    lengths, shared-prefix mixes, prefix cache AND speculation armed.
    Value = distinct prompt lengths served; the assertions are the
    point: every request terminal, ZERO serving new_shape events — the
    bucketing contract absorbs arbitrary request geometry without a
    single recompile."""
    from deeplearning4j_tpu.serving.replay import run_randomized_replay

    out = run_randomized_replay(n_requests=n_requests, gen_max=gen_max,
                                spec_k=spec_k)
    assert out["all_terminal"], (
        "randomized-shape replay left non-terminal requests: "
        f"{out['reasons']}")
    assert out["new_shape_events"] == 0, (
        "randomized request shapes leaked into a jit signature — "
        f"{out['new_shape_events']} serving new_shape event(s)")
    extra = {
        "requests": out["requests"],
        "prompt_lens": out["prompt_lens"],
        "gen_lens": out["gen_lens"],
        "generated_tokens": out["generated_tokens"],
        "prefix_hit_tokens": out["prefix_hit_tokens"],
        "first_compile_keys": out["first_compile_keys"],
        "new_shape_events": out["new_shape_events"],
    }
    return (float(len(out["prompt_lens"])),
            "generate_random_shapes_distinct_prompt_lens", extra)


def _bench_bert_import(layers: int, seq: int, d: int, heads: int, ff: int,
                       iters: int):
    """Imported-BERT forward throughput (BENCH_MODEL=bert_import): the
    SAME ONNX bytes imported twice — fusion off vs on (docs/OPTIMIZER.md
    § Fusion tier) — timed end-to-end on repeated forward passes. Value =
    tokens/sec WITH fusion; the JSON line carries the unfused rate, the
    speedup, and the fused_attention_count/fused_epilogue_count hit
    counters from OptimizeStats, so the import-path fast-kernel routing is
    a number, not a claim."""
    from deeplearning4j_tpu.imports.onnx_import import import_onnx
    from deeplearning4j_tpu.testing.onnx_builder import bert_onnx_model

    batch = 1
    model = bert_onnx_model(layers=layers, batch=batch, seq=seq, d=d,
                            heads=heads, ff=ff)
    r = np.random.RandomState(1)
    feeds = {"ids": r.randint(0, 512, (batch, seq)).astype(np.float32),
             "mask": (r.rand(batch, seq) > 0.1).astype(np.float32)}

    def run(sd):
        sd.output(feeds, ["y"])  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = sd.output(feeds, ["y"])["y"]
        dt = time.perf_counter() - t0
        assert np.isfinite(out).all()
        return batch * seq * iters / dt

    # pin BOTH legs explicitly — an ambient DL4J_TPU_FUSION=0 (the
    # documented opt-out) must not silently turn the "fused" leg into a
    # second unfused measurement (and a false regression assert)
    prev = os.environ.get("DL4J_TPU_FUSION")
    try:
        os.environ["DL4J_TPU_FUSION"] = "0"
        unfused_tps = run(import_onnx(model))
        os.environ["DL4J_TPU_FUSION"] = "1"
        sd = import_onnx(model)
        fused_tps = run(sd)
    finally:
        if prev is None:
            os.environ.pop("DL4J_TPU_FUSION", None)
        else:
            os.environ["DL4J_TPU_FUSION"] = prev
    st = sd.last_compile_stats
    att = st.fusions.get("attention", 0)
    epi = st.fusions.get("epilogue", 0)
    assert att >= layers, (
        f"attention fusion regressed: {att} < {layers} blocks matched "
        f"on a {layers}-layer imported BERT")
    extra = {"fused_attention_count": att, "fused_epilogue_count": epi,
             "tokens_per_sec_unfused": round(unfused_tps, 1),
             "fusion_speedup": round(fused_tps / unfused_tps, 3),
             "nodes_before": st.nodes_before, "nodes_after": st.nodes_after}
    return fused_tps, "bert_import_forward_tokens_per_sec", extra


def _bench_graph_compile(layers: int, width: int):
    """Graph-compile metric (docs/OPTIMIZER.md, `make bench-compile`): a
    redundant SameDiff graph — per-layer duplicated subexpressions, foldable
    constant chains, identity/transpose no-ops, dead branches, i.e. the
    shapes importers actually emit — is traced+compiled twice, optimizer off
    vs on. Value = wall speedup of trace+XLA-compile; the JSON line also
    carries the node counts so the win is a number, not a claim. CPU-safe
    (pure compile-time measurement, no training loop)."""
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    batch = 4

    def build(optimize: bool) -> SameDiff:
        r = np.random.RandomState(0)
        sd = SameDiff(optimize=optimize)
        h = sd.placeholder("x", (batch, width))
        for i in range(layers):
            w = sd.var(f"w{i}", r.randn(width, width).astype(np.float32) * 0.05)
            b = sd.var(f"b{i}", np.zeros(width, np.float32))
            c = sd.constant(f"c{i}", np.float32(width))
            scale = sd.math.sqrt(c)                   # foldable const chain
            pre = (h @ w + b) / scale
            t1 = sd.math.tanh(pre)
            t2 = sd.math.tanh(pre)                    # CSE duplicate
            g = sd.nn.sigmoid(t1 + t2)
            # no-op chain: the identity node and transpose pair are
            # stripped; the *1+0 arithmetic survives (placeholder-rooted,
            # so its dtype is unprovable — see docs/OPTIMIZER.md) exactly
            # as it would in an imported graph
            g = sd.op("identity", g) * 1.0 + 0.0
            g = g.transpose(1, 0).transpose(1, 0)
            _dead = sd.math.exp(pre) @ w              # dead branch
            h = g
        h.sum().rename("out")
        return sd

    feeds = {"x": np.random.RandomState(1).randn(batch, width)
             .astype(np.float32)}
    wall, outs, stats = {}, {}, {}
    for mode in (False, True):
        sd = build(mode)
        t0 = time.perf_counter()
        outs[mode] = sd.output(feeds, ["out"])["out"]
        wall[mode] = time.perf_counter() - t0
        stats[mode] = sd.last_compile_stats
    np.testing.assert_allclose(outs[False], outs[True], rtol=1e-5, atol=1e-5)

    # fusion gate (docs/OPTIMIZER.md § Fusion tier): a mini imported BERT
    # must report attention fusions — a matcher regression fails
    # `make bench-compile` (a gate-adjacent target), not just the separate
    # BENCH_MODEL=bert_import benchmark
    from deeplearning4j_tpu.imports.onnx_import import import_onnx
    from deeplearning4j_tpu.testing.onnx_builder import bert_onnx_model

    prev = os.environ.get("DL4J_TPU_FUSION")
    os.environ["DL4J_TPU_FUSION"] = "1"  # the gate must test the matcher
    try:                                 # even under an ambient opt-out
        mini = import_onnx(bert_onnx_model(layers=2, seq=8, d=64, heads=2,
                                           ff=128, vocab=64))
        r = np.random.RandomState(2)
        mini.output({"ids": r.randint(0, 64, (1, 8)).astype(np.float32),
                     "mask": np.ones((1, 8), np.float32)}, ["y"])
    finally:
        if prev is None:
            os.environ.pop("DL4J_TPU_FUSION", None)
        else:
            os.environ["DL4J_TPU_FUSION"] = prev
    att = mini.last_compile_stats.fusions.get("attention", 0)
    assert att >= 1, (
        f"fusion regression: imported 2-layer BERT reports {att} attention "
        f"fusions (expected >= 1)")

    extra = {"nodes_before": stats[True].nodes_before,
             "nodes_after": stats[True].nodes_after,
             "compile_s_unoptimized": round(wall[False], 3),
             "compile_s_optimized": round(wall[True], 3),
             "fused_attention_count": att}
    return wall[False] / wall[True], "graph_compile_optimizer_speedup", extra


# bf16 peak matmul TFLOP/s keyed by ``device_kind`` (Google Cloud
# documentation, "TPU v5e"). MFU = achieved model FLOP/s over this peak.
# Only the kind that is installed: a device not in the table is an error,
# never a default.
_PEAK_TFLOPS = {"TPU v5 lite": 197.0}


def _device_peak_tflops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_TFLOPS:
        raise KeyError(f"no published peak for device kind {kind!r}; known: "
                       f"{sorted(_PEAK_TFLOPS)}")
    return _PEAK_TFLOPS[kind]


def _model_flops_per_unit(metric: str, image: int) -> float:
    """Analytic training FLOPs per metric unit (image or token)."""
    if metric.startswith("resnet50"):
        # 4.1 GFLOP fwd @224 (standard count), train ~= 3x fwd
        return 4.1e9 * 3 * (image / 224.0) ** 2
    if metric.startswith("bert_base"):
        # 6 * params per token (fwd+bwd), BERT-base N=110M; attention terms
        # add a few % at seq 512 — the 6N convention is the scaling-book one
        return 6.0 * 110e6
    if metric.startswith("lenet5"):
        return 11e6 * 3  # ~11 MFLOP fwd per 28x28 image
    return 0.0


def _mfu(metric: str, value: float, image: int):
    per_unit = _model_flops_per_unit(metric, image)
    if not per_unit:
        return None
    return round(value * per_unit / (_device_peak_tflops() * 1e12), 4)


# unit by metric — module-level so the failure path can still label the line
_UNITS = {"resnet50_imagenet_train_images_per_sec": "images/sec/chip",
          "lenet5_mnist_train_images_per_sec": "images/sec/chip",
          "bert_base_mlm_train_tokens_per_sec": "tokens/sec/chip",
          "flash_attention_t8192_speedup_vs_generic": "x vs XLA generic",
          "graph_compile_optimizer_speedup": "x trace+compile speedup",
          "bert_import_forward_tokens_per_sec": "tokens/sec",
          "serving_fixed_qps_req_per_sec": "req/sec",
          "generate_open_loop_tokens_per_sec": "tokens/sec",
          "generate_overload_goodput_tokens_per_sec":
              "deadline-met tokens/sec",
          "generate_prefix_ttft_p50_speedup": "x TTFT p50 vs cache-off",
          "generate_spec_tokens_per_sec_speedup": "x tokens/sec vs spec-off",
          "generate_random_shapes_distinct_prompt_lens":
              "distinct prompt lens, 0 recompiles"}

_MODEL_METRIC = {"resnet50": "resnet50_imagenet_train_images_per_sec",
                 "lenet": "lenet5_mnist_train_images_per_sec",
                 "bert": "bert_base_mlm_train_tokens_per_sec",
                 "attention": "flash_attention_t8192_speedup_vs_generic",
                 "graph_compile": "graph_compile_optimizer_speedup",
                 "bert_import": "bert_import_forward_tokens_per_sec",
                 "serving": "serving_fixed_qps_req_per_sec",
                 "generate": "generate_open_loop_tokens_per_sec",
                 "generate_overload":
                     "generate_overload_goodput_tokens_per_sec",
                 "generate_prefix": "generate_prefix_ttft_p50_speedup",
                 "generate_spec": "generate_spec_tokens_per_sec_speedup",
                 "generate_random_shapes":
                     "generate_random_shapes_distinct_prompt_lens"}


def main() -> None:
    from deeplearning4j_tpu.environment import enable_compile_cache

    try:
        device = require_tpu()
    except NoAcceleratorError as e:
        print(f"bench.py: {e}", file=sys.stderr)
        raise SystemExit(4)
    enable_compile_cache()
    model = os.environ.get("BENCH_MODEL", "resnet50")
    # the documented spellings are BENCH_MODEL=generate BENCH_OVERLOAD=1
    # (goodput ramp) and BENCH_MODEL=generate BENCH_PREFIX=1 (shared-
    # prompt replay); the canonical metric keys apply either way
    if model == "generate" and os.environ.get("BENCH_OVERLOAD") == "1":
        model = "generate_overload"
    elif model == "generate" and os.environ.get("BENCH_PREFIX") == "1":
        model = "generate_prefix"
    elif model == "generate" and os.environ.get("BENCH_SPEC") == "1":
        model = "generate_spec"
    elif model == "generate" and os.environ.get("BENCH_RANDOM_SHAPES") == "1":
        model = "generate_random_shapes"
    dtype = os.environ.get("BENCH_DTYPE", "mixed")
    iters = int(os.environ.get("BENCH_ITERS", "60"))
    image = int(os.environ.get("BENCH_IMAGE", "224"))

    # Per-model default batch: the timed window must dwarf the dispatch
    # cost or the number measures jitter, not the device (LeNet at batch
    # 128 × 60 steps is ~80ms of device work). 4096 puts LeNet's window at
    # ~2.5s; ResNet's 128×60 is already ~2.8s.
    default_batch = {"lenet": 4096}.get(model, 128)
    batch = int(os.environ.get("BENCH_BATCH", str(default_batch)))

    extra = {}
    try:
        if model == "lenet":
            value, metric = _bench_lenet(batch, iters)
            method = f"b{batch}i{iters}"
        elif model == "attention":
            value, metric = _bench_attention(iters)
            method = f"i{iters}"
        elif model == "bert":
            bb = int(os.environ.get("BENCH_BERT_BATCH", "16"))
            seq = int(os.environ.get("BENCH_SEQ", "512"))
            value, metric = _bench_bert(bb, iters, dtype, seq)
            method = f"b{bb}s{seq}i{iters}{'' if dtype == 'mixed' else dtype}"
        elif model == "graph_compile":
            layers = int(os.environ.get("BENCH_GRAPH_LAYERS", "6"))
            width = int(os.environ.get("BENCH_GRAPH_WIDTH", "192"))
            value, metric, extra = _bench_graph_compile(layers, width)
            method = f"L{layers}w{width}"
        elif model == "bert_import":
            bl = int(os.environ.get("BENCH_IMPORT_LAYERS",
                                    "12"))
            seq = int(os.environ.get("BENCH_SEQ", "128"))
            bd = int(os.environ.get("BENCH_IMPORT_D",
                                    "768"))
            bh = int(os.environ.get("BENCH_IMPORT_HEADS",
                                    "12"))
            bff = int(os.environ.get("BENCH_IMPORT_FF",
                                     "3072"))
            value, metric, extra = _bench_bert_import(bl, seq, bd, bh, bff,
                                                      iters)
            method = f"L{bl}s{seq}d{bd}i{iters}"
        elif model == "serving":
            qps = float(os.environ.get("BENCH_QPS", "200"))
            nreq = int(os.environ.get("BENCH_REQUESTS",
                                      "1000"))
            mb = int(os.environ.get("BENCH_MAX_BATCH",
                                    "32"))
            value, metric, extra = _bench_serving(qps, nreq, mb)
            method = f"q{qps:g}n{nreq}b{mb}"
        elif model == "generate":
            qps = float(os.environ.get("BENCH_QPS", "16"))
            nreq = int(os.environ.get("BENCH_REQUESTS",
                                      "64"))
            gen = int(os.environ.get("BENCH_GEN_TOKENS",
                                     "64"))
            slots = int(os.environ.get("BENCH_SLOTS", "16"))
            preset = os.environ.get("BENCH_GPT",
                                    "base")
            value, metric, extra = _bench_generate(qps, nreq, gen, slots,
                                                   preset)
            method = f"q{qps:g}n{nreq}g{gen}s{slots}{preset}"
        elif model == "generate_prefix":
            nreq = int(os.environ.get("BENCH_REQUESTS",
                                      "32"))
            npfx = int(os.environ.get("BENCH_PREFIX_COUNT", "3"))
            slen = int(os.environ.get("BENCH_PREFIX_SYS", "88"))
            gen = int(os.environ.get("BENCH_GEN_TOKENS", "4"))
            value, metric, extra = _bench_generate_prefix(nreq, npfx, slen,
                                                          gen)
            method = f"n{nreq}p{npfx}s{slen}g{gen}"
        elif model == "generate_spec":
            nreq = int(os.environ.get("BENCH_REQUESTS",
                                      "16"))
            gen = int(os.environ.get("BENCH_GEN_TOKENS", "12"))
            k = int(os.environ.get("BENCH_SPEC_K", "4"))
            value, metric, extra = _bench_generate_spec(nreq, gen, k)
            method = f"n{nreq}g{gen}k{k}"
        elif model == "generate_random_shapes":
            nreq = int(os.environ.get("BENCH_REQUESTS",
                                      "48"))
            gen = int(os.environ.get("BENCH_GEN_TOKENS", "6"))
            k = int(os.environ.get("BENCH_SPEC_K", "3"))
            value, metric, extra = _bench_generate_random_shapes(nreq, gen,
                                                                 k)
            method = f"n{nreq}g{gen}k{k}"
        elif model == "generate_overload":
            nreq = int(os.environ.get("BENCH_REQUESTS",
                                      "64"))
            gen = int(os.environ.get("BENCH_GEN_TOKENS",
                                     "32"))
            slots = int(os.environ.get("BENCH_SLOTS", "8"))
            factor = float(os.environ.get("BENCH_OVERLOAD_FACTOR", "2.5"))
            slow = os.environ.get("BENCH_SLOW_DECODE", "1") != "0"
            value, metric, extra = _bench_generate_overload(
                nreq, gen, slots, factor, slow_decode=slow)
            method = f"n{nreq}g{gen}s{slots}x{factor:g}" + \
                ("" if slow else "raw")
        else:
            value, metric = _bench_resnet50(batch, iters, image, dtype)
            method = f"b{batch}x{image}i{iters}{'' if dtype == 'mixed' else dtype}"
    except Exception as e:  # noqa: BLE001 — the one-JSON-line contract:
        # an individual benchmark failure must still emit the final
        # machine-parsable line (every BENCH round so far recorded
        # `parsed: null` because the crash pre-empted it)
        metric = _MODEL_METRIC.get(model, model)
        line = {"metric": metric, "value": None,
                "unit": _UNITS.get(metric, ""), "vs_baseline": None,
                "error": f"{type(e).__name__}: {e}"[:500]}
        print(json.dumps(line))
        raise SystemExit(2)

    record = os.environ.get("BENCH_RECORD", "1") != "0"
    hist_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.json")
    hist = {}
    if os.path.exists(hist_path):
        try:
            hist = json.load(open(hist_path))
        except Exception:
            hist = {}
    # RATCHET against the max-watermark, not the previous run — a regression
    # reports <1.0 on EVERY run until fixed instead of resetting its own
    # baseline (round-2 verdict weak #7)
    entry = hist.get(metric)
    if isinstance(entry, dict):
        watermark = entry.get("watermark", 0.0)
        runs = entry.get("runs", [])
        # A watermark is only comparable within one measurement methodology
        # (batch/seq/iters/dtype). When the method changes, the old series
        # would report nonsense ratios (e.g. a window-size change once read
        # as a 60× "speedup"), so start a fresh series — the old one stays
        # in git history.
        if entry.get("method") != method:
            watermark, runs = 0.0, []
    else:  # legacy scalar entry
        watermark = float(entry) if entry else 0.0
        runs = []
    vs_baseline = value / watermark if watermark else 1.0
    nd = 3 if value < 100 else 1  # keep ratio metrics' ratchet sensitive
    if record:
        runs = (runs + [round(value, nd)])[-20:]
        try:
            hist[metric] = {"watermark": round(max(watermark, value), nd),
                            "runs": runs, "method": method}
            json.dump(hist, open(hist_path, "w"), indent=1)
        except Exception:
            pass

    line = {
        "metric": metric,
        "value": round(value, 3 if value < 100 else 1),
        "unit": _UNITS[metric],
        "vs_baseline": round(vs_baseline, 3),
    }
    line["device"] = {"platform": device.platform,
                      "kind": device.device_kind}
    line.update(extra)
    mfu = _mfu(metric, value, image)
    if mfu is not None:
        line["mfu"] = mfu
    # embed the observe/ snapshot (recompiles, step + serving latency
    # percentiles) so the bench trajectory carries latency, not just
    # throughput — docs/OBSERVABILITY.md
    from deeplearning4j_tpu import observe

    obs = observe.summary()
    if obs:
        line["observe"] = obs
    print(json.dumps(line))


if __name__ == "__main__":
    main()
