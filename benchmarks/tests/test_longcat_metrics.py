"""The ``longcat`` family's readers and counts (PR 27), each on a small
hand-recorded trace (plain form, names as the v5e's trace prints them) and
hand-recorded spans."""

import json
import os

import pytest

import common
import trace_reduce

CFG = common.load_json(os.path.join(common.HERE, "configs",
                                    "longcat-flash-omni.json"))
CELL = {"cfg": CFG, "mix": {}}

KERNEL = ('%latent_decode_attention.{n} = bf16[128,64,512]{{2,1,0:T(8,128)'
          '(2,1)}} custom-call(%copy.1, %copy-done, %fusion.7, %kv.1), '
          'custom_call_target="tpu_custom_call"')
# GPT's paged kernel (no name of its own) and an operand of the latent pool's
# shape: neither may be read as the latent kernel
OTHER = ('%decode.13 = f32[32,1,768]{2,1,0:T(8,128)} custom-call(%a, %b), '
         'custom_call_target="tpu_custom_call"')
POOL_SHAPED = ('%fusion.9 = bf16[8,1,12289,16,640]{4,3,2,1,0:T(8,128)(2,1)} '
               'fusion(%p), kind=kLoop, metadata={op_name="jit(decode)/'
               'latent_decode_attention/concatenate"}')


def traced(events):
    trace = {"devices": {"/device:TPU:0": events},
             "host": [("bench_window", 0, 1_000_000_000)]}
    return trace_reduce.reduce_trace(trace)


def span(name, **args):
    return {"name": name, "start": 0.0, "seconds": 0.01, "args": args}


def test_counts_follow_the_issues_arithmetic():
    counts = common.module("counts", "longcat")
    # one MLA sub-layer 90.57 M parameters, one dense feed-forward 226.5 M,
    # the router 4.7 M, an expert 37.75 M at 12 * 16 / 768 picks a token
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 64 * 128 * 6144)
    assert mla == 90_570_752
    per_layer = 2 * mla + 2 * 3 * 6144 * 12288 + 6144 * 768 \
        + 0.25 * 3 * 6144 * 2048
    assert counts._dense_per_token(CFG) == pytest.approx(2 * 4 * per_layer)
    head = 2 * 6144 * 16384
    # a generated token attends over its context with the projections
    # absorbed: 64 heads x (576 scores + 512 values) a position a sub-layer
    assert counts.decode_flops(CFG, 1000) == pytest.approx(
        2 * 4 * per_layer + 2 * 8 * 64 * 1088 * 1000 + head)
    # a prompt with keys and values materialised: 192 + 128 a head a pair
    assert counts.prompt_flops(CFG, 100) == pytest.approx(
        100 * 2 * 4 * per_layer + 2 * 8 * 64 * 320 * 100 * 101 / 2 + head)
    # the unpadded row: 8 sub-layers x 576 values x 2 bytes a position
    assert counts.latent_bytes_per_token(CFG, 1000) == 8 * 1000 * 576 * 2


def test_mfu_reads_the_family_counts():
    read = common.module("layer_metrics", "mfu").read
    counts = common.module("counts", "longcat")
    # one request: prompt of 40, first token at 0.5, two more inside the
    # window and one after it
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "chips": 1,
           "window": (0.0, 2.0), "tokens": [(40, [0.5, 1.0, 1.5, 2.5])]}
    want = (counts.prompt_flops(CFG, 40) + counts.decode_flops(CFG, 41)
            + counts.decode_flops(CFG, 42)) / 2.0
    assert read(ctx, flops_per_s="serve_flops_per_s") == pytest.approx(
        100.0 * want / 197e12)
    assert read(dict(ctx, tokens=[]), flops_per_s="serve_flops_per_s") is None


def test_latent_attn_roofline_finds_the_kernel_by_its_name():
    read = common.module("layer_metrics", "latent_attn_roofline.serve").read
    tr = traced([(KERNEL.format(n=8), 1000, 400_000),
                 (KERNEL.format(n=9), 500_000, 600_000),
                 (OTHER, 2_000_000, 900_000),
                 (POOL_SHAPED, 3_000_000, 900_000)])
    # two decode tokens arrived inside the traced second, at contexts 301
    # and 302; the first token (prefill) and one outside do not count
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "trace": tr,
           "traced": (10.0, 11.0),
           "tokens": [(300, [9.5, 10.2, 10.8, 11.5])]}
    need = 8 * 576 * 2 * (301 + 302)
    assert read(ctx) == pytest.approx(100.0 * (need / 819e9) / 1e-3)
    # nothing of that name: nothing returned, never 0 and never another
    # kernel's or an operand's shape
    assert read(dict(ctx, trace=traced([(OTHER, 0, 5000),
                                        (POOL_SHAPED, 9000, 500)]))) is None
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, tokens=[(300, [9.5, 11.5])])) is None


def test_expert_readers_on_recorded_spans():
    tokens = common.module("layer_metrics",
                           "moe_tokens_per_expert_step.serve").read
    ratio = common.module("layer_metrics",
                          "moe_load_max_over_mean.serve").read
    spans = [
        span("serving_decode", slots=128, moe_held=128, moe_zero=2048,
             moe_absent=3968, moe_max_over_mean=3.0),
        span("serving_decode", slots=128, moe_held=64, moe_zero=2100,
             moe_absent=3980, moe_max_over_mean=5.0),
        # a prefill's arguments are not a decode step's
        span("serving_prefill", prompt_len=200, moe_held=900, moe_zero=1,
             moe_absent=1, moe_max_over_mean=9.0),
        span("serving_step"),
    ]
    ctx = {"cell": CELL, "spans": spans}
    # 96 held picks a step over 16 held experts x 4 expert layers
    assert tokens(ctx) == pytest.approx(96 / 64)
    assert ratio(ctx) == pytest.approx(4.0)
    # a program without an expert layer (GPT's spans; the parent's): nothing
    plain = {"cell": CELL, "spans": [span("serving_decode", slots=32)]}
    assert tokens(plain) is None and ratio(plain) is None
    assert tokens({"cell": CELL, "spans": []}) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.load_cell("longcat-flash-omni.reason-closed")
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_tokens_per_expert_step.serve", "moe_load_max_over_mean.serve",
            "mfu_pct.serve", "decode_step_ms.serve"} <= names
    # GPT's kernel is not this model's; and the cell reports no first-token
    # tail (it hops), so nor the metrics that move it
    assert not names & {"paged_attn_roofline.serve", "prefill_ms.serve",
                        "queue_wait_p95_ms.serve"}
    assert {m["name"] for m in cell["end_to_end"]} >= {"serve_tokens_per_s",
                                                       "setup_s"}
    for m in manifest["per_layer"]:
        if m["name"].startswith(("moe_", "latent_")):
            assert m["workloads"] == ["longcat-flash-omni.reason-closed"]
    assert len(json.dumps(manifest)) < 64 * 1024


# ------------------------------------------------- the cell, tiny, on the CPU


def tiny_cell():
    """The cell's own files with the widths cut; every mechanism kept (two
    MLA sub-layers a layer, 4 held of 16 routed experts + 8 zero, top-3, a
    sliced vocabulary). The limit is the tiny size's own."""
    import copy

    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32,
               num_layers=2, num_attention_heads=4, kv_lora_rank=32,
               q_lora_rank=48, qk_rope_head_dim=16, qk_nope_head_dim=16,
               v_head_dim=16, n_routed_experts=4, zero_expert_num=8,
               moe_topk=3, vocab_size=256,
               published={"num_layers": 28, "n_routed_experts": 16,
                          "vocab_size": 2048},
               limits={"served_logit_gap": 0.08})
    mix = copy.deepcopy(common.load_json(os.path.join(
        common.HERE, "traffic", "reason-closed.json")))
    mix.update(
        clients=4, pool=16, ramp_seconds=0.5, max_total=128, check_requests=6,
        prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.5,
                    "min": 8, "max": 64},
        new_tokens={"dist": "lognormal", "median": 24, "sigma": 0.5,
                    "min": 8, "max": 48},
        engine={"max_slots": 4, "page_size": 8, "max_pages_per_seq": 16,
                "max_prompt": 64, "prefix_pages": 0, "spec_k": 0})
    return {"name": "tiny.longcat", "chips": 1, "cfg": cfg, "mix": mix,
            "per_layer": [], "end_to_end": []}


def test_tiny_cell_is_correct_and_every_stand_in_is_not():
    import jax

    cell, seed = tiny_cell(), 2**31 + 11
    loop = common.module("loops", "serve_closed_loop")
    res = loop.run(cell, seed=seed, seconds=2.0, trace=False,
                   devs=jax.devices())
    assert res["checks"].correct, res["checks"].compared()
    assert res["failed"] == 0 and res["attempted"] > 0
    ctx = res["ctx"]
    # the readers on the program's own spans: top-3 of 12 outputs, 4 held
    per_step = common.module(
        "layer_metrics", "moe_tokens_per_expert_step.serve").read(ctx)
    assert 0 < per_step <= 4 * 3 / 4
    assert common.module(
        "layer_metrics", "moe_load_max_over_mean.serve").read(ctx) >= 1.0
    assert common.module("layer_metrics", "programs_compiled").read(ctx) > 0
    family = common.module("families", "longcat")
    tags = []
    for tag, readings, kw in family.stand_ins(cell["cfg"], cell["mix"], seed,
                                              ctx):
        checks = common.Checks()
        family.verify(cell["cfg"], cell["mix"], seed, readings, checks, **kw)
        assert checks.correct is False, (tag, checks.compared())
        tags.append(tag)
    assert tags == ["control_float8", "control_int8",
                    "fault_no_zero_experts", "fault_no_kv_scale"]
