"""The ``xing`` family's counts and the two readers PR 31 adds, each on a
small hand-recorded trace (plain form, names as the v5e's trace prints them)
and hand-recorded spans; then the cell's own files at a tiny size on the
CPU."""

import json
import os

import pytest

import common
import trace_reduce

CFG = common.load_json(os.path.join(common.HERE, "configs",
                                    "xing4.0-29b-a4b.json"))
CELL = {"cfg": CFG, "mix": {}}
NAME = "xing4.0-29b-a4b.reason-closed"

KERNEL = ('%latent_decode_attention.{n} = bf16[128,32,512]{{2,1,0:T(8,128)'
          '(2,1)}} custom-call(%copy.1, %copy-done, %fusion.7, %kv.1), '
          'custom_call_target="tpu_custom_call"')
# a grouped product (a Mosaic call too) and an operand of the latent pool's
# shape: neither may be read as the latent kernel
OTHER = ('%ragged-dot-none.10 = f32[512,1024]{1,0:T(8,128)} custom-call(%a, '
         '%b), custom_call_target="tpu_custom_call"')
POOL_SHAPED = ('%fusion.9 = bf16[6,1,12289,16,640]{4,3,2,1,0:T(8,128)(2,1)} '
               'fusion(%p), kind=kLoop, metadata={op_name="jit(decode)/'
               'latent_decode_attention/concatenate"}')


def traced(events):
    trace = {"devices": {"/device:TPU:0": events},
             "host": [("bench_window", 0, 1_000_000_000)]}
    return trace_reduce.reduce_trace(trace)


def span(name, **args):
    return {"name": name, "start": 0.0, "seconds": 0.01, "args": args}


def test_counts_follow_the_issues_arithmetic():
    counts = common.module("counts", "xing")
    # ISSUE 31's cut: MLA 28.4 M, an expert layer 745.0 M, the dense layer
    # 128.2 M, embedding and head 939.5 M: 4.79 B parameters, 9.58 GB
    assert counts.mla_params(CFG) == (3584 * 768 + 768 * 6144 + 3584 * 576
                                      + 512 * 8192 + 4096 * 3584) == 28_409_856
    assert counts.expert_params(CFG) == 3 * 3584 * 1024 == 11_010_048
    assert counts.hc_params(CFG) == 14336 * 24
    assert counts.expert_layer_params(CFG) == pytest.approx(745.0e6, rel=5e-4)
    assert counts.dense_layer_params(CFG) == pytest.approx(128.2e6, rel=5e-4)
    assert counts.outer_params(CFG) == pytest.approx(939.5e6, rel=5e-4)
    total = (counts.outer_params(CFG) + counts.dense_layer_params(CFG)
             + 5 * counts.expert_layer_params(CFG))
    assert total == pytest.approx(4.79e9, rel=2e-3)
    assert 2 * total == pytest.approx(9.58e9, rel=2e-3)
    # a token: MLA and two hyper-connected sub-layers a layer (the n*d x 24
    # product, n*d to read the mix, n*n*d + n*d to write the streams); the
    # dense layer's feed-forward; an expert layer's router, 4 of 64 experts
    # and the shared one
    hc = 14336 * 24 + 14336 + 4 * 14336 + 14336
    every = 28_409_856 + 2 * hc
    per_token = ((every + 3 * 3584 * 9216)
                 + 5 * (every + 3584 * 64 + 5 * 11_010_048))
    assert counts._dense_per_token(CFG) == pytest.approx(2 * per_token)
    head = 2 * 3584 * 131072
    # a generated token attends over its context with the projections
    # absorbed: 32 heads x (576 scores + 512 values) a position a layer
    assert counts.decode_flops(CFG, 1000) == pytest.approx(
        2 * per_token + 2 * 6 * 32 * 1088 * 1000 + head)
    # a prompt with keys and values materialised: 192 + 128 a head a pair
    assert counts.prompt_flops(CFG, 100) == pytest.approx(
        100 * 2 * per_token + 2 * 6 * 32 * 320 * 100 * 101 / 2 + head)
    # the unpadded row: 6 layers x 576 values x 2 bytes a position
    assert counts.latent_bytes_per_token(CFG, 1000) == 6 * 1000 * 576 * 2


def test_mfu_reads_the_family_counts():
    read = common.module("layer_metrics", "mfu").read
    counts = common.module("counts", "xing")
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "chips": 1,
           "window": (0.0, 2.0), "tokens": [(40, [0.5, 1.0, 1.5, 2.5])]}
    want = (counts.prompt_flops(CFG, 40) + counts.decode_flops(CFG, 41)
            + counts.decode_flops(CFG, 42)) / 2.0
    assert read(ctx, flops_per_s="serve_flops_per_s") == pytest.approx(
        100.0 * want / 197e12)
    assert read(dict(ctx, tokens=[]), flops_per_s="serve_flops_per_s") is None


def test_latent_kernel_roofline_finds_the_kernel_by_its_name():
    read = common.module("layer_metrics", "latent_kernel_roofline.serve").read
    tr = traced([(KERNEL.format(n=8), 1000, 400_000),
                 (KERNEL.format(n=9), 500_000, 600_000),
                 (OTHER, 2_000_000, 900_000),
                 (POOL_SHAPED, 3_000_000, 900_000)])
    # two decode tokens arrived inside the traced second, at contexts 301
    # and 302; the first token (prefill) and one outside do not count
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "trace": tr,
           "traced": (10.0, 11.0),
           "tokens": [(300, [9.5, 10.2, 10.8, 11.5])]}
    need = 6 * 576 * 2 * (301 + 302)       # this family's six layers
    assert read(ctx) == pytest.approx(100.0 * (need / 819e9) / 1e-3)
    # the same trace under LongCat's configuration reads its family's bytes
    # (eight sub-layers), as the older reader does
    lc = {"cfg": common.load_json(os.path.join(
        common.HERE, "configs", "longcat-flash-omni.json")), "mix": {}}
    old = common.module("layer_metrics", "latent_attn_roofline.serve").read
    assert read(dict(ctx, cell=lc)) == pytest.approx(old(dict(ctx, cell=lc)))
    assert read(dict(ctx, cell=lc)) == pytest.approx(read(ctx) * 8 / 6)
    # nothing of that name: nothing returned, never 0 and never another
    # kernel's or an operand's shape
    assert read(dict(ctx, trace=traced([(OTHER, 0, 5000),
                                        (POOL_SHAPED, 9000, 500)]))) is None
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, tokens=[(300, [9.5, 11.5])])) is None


def test_hc_sinkhorn_residual_reads_the_decode_spans():
    read = common.module("layer_metrics", "hc_sinkhorn_residual.serve").read
    spans = [
        span("serving_decode", slots=128, hc_residual=2e-6, hc_clamped=0),
        span("serving_decode", slots=128, hc_residual=7e-3, hc_clamped=0),
        span("serving_decode", slots=128, hc_residual=4e-4, hc_clamped=3),
        # a prefill's arguments are not a decode step's
        span("serving_prefill", prompt_len=200, hc_residual=0.5,
             hc_clamped=0),
        span("serving_step"),
    ]
    assert read({"cell": CELL, "spans": spans}) == pytest.approx(7e-3)
    # a program without a hyper-connected residual (GPT's, LongCat's spans;
    # the parent's): nothing
    plain = [span("serving_decode", slots=32, moe_held=5)]
    assert read({"cell": CELL, "spans": plain}) is None
    assert read({"cell": CELL, "spans": []}) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.load_cell(NAME)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {
        "decode_step_ms.serve", "itl_p95_ms.serve", "slot_occupancy_pct.serve",
        "helper_tpu_dispatches.serve", "mfu_pct.serve",
        "device_idle_pct.serve", "step_host_ms.serve",
        "programs_compiled.serve", "moe_load_max_over_mean.serve",
        "latent_kernel_roofline.serve", "hc_sinkhorn_residual.serve"}
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the two readers bound to LongCat's counts and keys stay LongCat's
    for old in ("latent_attn_roofline.serve",
                "moe_tokens_per_expert_step.serve"):
        assert by_name[old]["workloads"] == [
            "longcat-flash-omni.reason-closed"]
    for new in ("latent_kernel_roofline.serve", "hc_sinkhorn_residual.serve"):
        assert by_name[new]["workloads"] == [NAME]
        assert by_name[new]["moves"] == "serve_tokens_per_s"
    # every reader of the cell is a file found by the metric's name
    for name in names:
        stem = os.path.join(common.HERE, "layer_metrics", name)
        assert os.path.exists(stem + ".py") or os.path.exists(stem + ".json")
    # the configuration: every number of the source's config but the two
    # reduced keys, which `published` restores
    entry = cell["config_entry"]
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace"]
    assert CFG["published"] == {"num_hidden_layers": 40,
                                "first_k_dense_replace": 2}
    assert (CFG["n_routed_experts"], CFG["num_experts_per_tok"],
            CFG["vocab_size"], CFG["hidden_size"]) == (64, 4, 131072, 3584)
    assert len(json.dumps(manifest)) < 64 * 1024


# ------------------------------------------------- the cell, tiny, on the CPU


def tiny_cell():
    """The cell's own files with the widths cut; every mechanism kept (one
    dense and two expert layers, 16 experts top-4 and the shared one, four
    streams with 20 iterations, YaRN). The limit is the tiny size's own."""
    import copy

    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=32,
               q_lora_rank=48, qk_rope_head_dim=16, qk_nope_head_dim=16,
               v_head_dim=16, n_routed_experts=16, vocab_size=256,
               limits={"served_logit_gap": 0.12})
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
    return {"name": "tiny.xing", "chips": 1, "cfg": cfg, "mix": mix,
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
    # the readers on the program's own spans
    residual = common.module(
        "layer_metrics", "hc_sinkhorn_residual.serve").read(ctx)
    assert 0 < residual < 0.2
    assert common.module(
        "layer_metrics", "moe_load_max_over_mean.serve").read(ctx) >= 1.0
    assert common.module("layer_metrics", "programs_compiled").read(ctx) > 0
    assert common.module("layer_metrics", "mfu").read(
        dict(ctx, kind="TPU v5 lite"), flops_per_s="serve_flops_per_s") > 0
    decode = [s for s in ctx["spans"] if s["name"] == "serving_decode"]
    assert decode and all(
        {"hc_residual", "hc_clamped", "moe_held", "moe_max_over_mean"}
        <= set(s["args"]) for s in decode)
    assert all(s["args"]["moe_absent"] == 0 == s["args"]["moe_zero"]
               for s in decode)
    family = common.module("families", "xing")
    tags = []
    for tag, readings, kw in family.stand_ins(cell["cfg"], cell["mix"], seed,
                                              ctx):
        checks = common.Checks()
        family.verify(cell["cfg"], cell["mix"], seed, readings, checks, **kw)
        assert checks.correct is False, (tag, checks.compared())
        tags.append(tag)
    assert tags == ["control_float8", "control_int8", "fault_no_sinkhorn",
                    "fault_static_hc", "fault_no_shared_expert",
                    "fault_no_renorm", "fault_no_yarn"]
