"""The ``sdar`` family's counts and the two readers its cell adds, each on a
small hand-recorded trace (plain form, names as the v5e's trace prints them)
and hand-recorded spans; then the cell's own files at a tiny size on the
CPU, through the loop, the family, the comparison and every stand-in."""

import json
import os

import pytest

import common
import trace_reduce

CFG = common.load_json(os.path.join(common.HERE, "configs",
                                    "sdar-30b-a3b-chat.json"))
CELL = {"cfg": CFG, "mix": {}}
NAME = "sdar-30b-a3b-chat.reason-closed"

KERNEL = ('%block_decode_attention.{n} = bf16[128,128,128]{{2,1,0:T(8,128)(2,'
          '1)}} custom-call(%a, %b, %c, %q, %kb, %kv), '
          'custom_call_target="tpu_custom_call"')
# another Mosaic call, and a fusion inside the kernel's named scope: neither
# may be read as the block kernel
OTHER = ('%grouped_gate_up.10 = bf16[4096,768]{1,0:T(8,128)(2,1)} custom-call('
         '%a, %b), custom_call_target="tpu_custom_call"')
SCOPED = ('%multiply_fusion = bf16[128,2,16,512]{3,2,1,0:T(8,128)(2,1)} '
          'fusion(%p), kind=kLoop, metadata={op_name="jit(decode)/'
          'block_decode_attention/pad"}')


def traced(events):
    trace = {"devices": {"/device:TPU:0": events},
             "host": [("bench_window", 0, 1_000_000_000)]}
    return trace_reduce.reduce_trace(trace)


def span(name, **args):
    return {"name": name, "start": 0.0, "seconds": 0.01, "args": args}


def test_counts_follow_the_schedule():
    """A block is 5 passes of 4 rows a slot and 4 heads' worth of logits a
    row; a pass's attention reads the context's rows unpadded."""
    counts = common.module("counts", "sdar")
    d, hq, hkv, hd, w = 2048, 32, 4, 128, 768
    proj = d * hq * hd * 2 + d * hkv * hd * 2
    row = 2.0 * 6 * (proj + d * 128 + 8 * 3 * d * w
                     + 2 * hq * hd * (700 + 4))
    head = 2.0 * d * 151936
    assert counts.block_flops(CFG, 700) == pytest.approx(
        5 * 4 * row + 4 * 4 * head)
    # one layer of one pass: the 18.87 M multiply-adds of the projections
    assert proj == 18_874_368
    assert counts.block_bytes_per_pass(CFG, 700) == 6 * 2 * 512 * 2 * 704
    # tokens at positions 37 .. 44 of a prompt of 37: blocks at 36, 40, 44
    assert counts.blocks_of(CFG, 37, [1.0, 1.0, 1.0, 2.0, 2.0, 2.0, 2.0,
                                      3.0]) == [(36, 1.0), (40, 2.0),
                                                (44, 3.0)]
    # a prompt's whole blocks, once, no head
    assert counts.prompt_flops(CFG, 3) == 0.0
    assert counts.prompt_flops(CFG, 9) > counts.prompt_flops(CFG, 8) * 0.99


def test_mfu_reads_the_family_counts():
    read = common.module("layer_metrics", "mfu").read
    counts = common.module("counts", "sdar")
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "chips": 1,
           "window": (0.0, 2.0),
           "tokens": [(38, [0.5, 0.5, 1.0, 1.0, 1.0, 1.0, 2.5])]}
    want = (counts.prompt_flops(CFG, 38) + counts.block_flops(CFG, 36)
            + counts.block_flops(CFG, 40)) / 2.0
    assert read(ctx, flops_per_s="serve_flops_per_s") == pytest.approx(
        100.0 * want / 197e12)
    assert read(dict(ctx, tokens=[]), flops_per_s="serve_flops_per_s") is None


def test_block_attn_roofline_finds_the_kernel_by_its_name():
    read = common.module("layer_metrics", "block_attn_roofline.serve").read
    counts = common.module("counts", "sdar")
    # two block steps of six layers' calls, 0.5 ms each
    events = [(KERNEL.format(n=3 + i), 1000 + i * 1_000_000, 500_000)
              for i in range(12)]
    tr = traced(events + [(OTHER, 50_000_000, 900_000),
                          (SCOPED, 52_000_000, 900_000)])
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "trace": tr,
           "traced": (0.0, 1.0),
           "tokens": [(100, [0.2] * 4 + [0.6] * 4 + [1.5] * 4),
                      (64, [0.3] * 4)]}
    need = 5 * (counts.block_bytes_per_pass(CFG, 100 // 4 * 4)
                + counts.block_bytes_per_pass(CFG, 104)
                + counts.block_bytes_per_pass(CFG, 64))
    assert read(ctx) == pytest.approx(100.0 * (need / 819e9) / 6e-3)
    # nothing of that name: nothing returned, never 0 and never another
    # kernel's or a scope's; no trace or no tokens in the window: nothing
    assert read(dict(ctx, trace=traced([(OTHER, 0, 5000),
                                        (SCOPED, 9000, 500)]))) is None
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, tokens=[(100, [5.0] * 4)])) is None


def test_diffusion_tokens_per_pass_reads_the_decode_spans():
    read = common.module("layer_metrics",
                         "diffusion_tokens_per_pass.serve").read
    spans = [span("serving_decode", slots=128, dd_passes=5, dd_masked=512,
                  dd_tokens=512),
             span("serving_decode", slots=128, dd_passes=5, dd_masked=509,
                  dd_tokens=509),
             # a prefill's arguments are not a block step's
             span("serving_prefill", prompt_len=200, dd_passes=1,
                  dd_masked=0, dd_tokens=0),
             span("serving_step")]
    assert read({"spans": spans}) == pytest.approx(1021 / 1280)
    # a program that yields a token a step (the other cells, the parent):
    # nothing
    plain = [span("serving_decode", slots=32, moe_held=5)]
    assert read({"spans": plain}) is None
    assert read({"spans": []}) is None


def test_the_manifest_lists_the_cell_under_every_metric_it_reports():
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.load_cell(NAME)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {
        "decode_step_ms.serve", "itl_p95_ms.serve", "slot_occupancy_pct.serve",
        "helper_tpu_dispatches.serve", "mfu_pct.serve",
        "device_idle_pct.serve", "moe_load_max_over_mean.serve",
        "step_host_ms.serve", "programs_compiled.serve", "step_max_ms.serve",
        "step_host_cpu_pct.serve", "step_gc_pct.serve",
        "block_attn_roofline.serve", "diffusion_tokens_per_pass.serve"}
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    assert cell["chips"] == 1 and cell["traffic"] == "reason-closed"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for new, layer in (("block_attn_roofline.serve", "kernels"),
                       ("diffusion_tokens_per_pass.serve", "serving engine")):
        assert by_name[new]["workloads"] == [NAME]
        assert by_name[new]["moves"] == "serve_tokens_per_s"
        assert by_name[new]["layer"] == layer
    assert manifest["workloads"][-1]["name"] == NAME
    assert manifest["configs"][-1]["name"] == "sdar-30b-a3b-chat"
    assert [m["name"] for m in manifest["per_layer"][-2:]] == [
        "block_attn_roofline.serve", "diffusion_tokens_per_pass.serve"]
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if NAME in m.get("workloads", []):
            assert m["workloads"][-1] == NAME
    for name in names:
        stem = os.path.join(common.HERE, "layer_metrics", name)
        assert os.path.exists(stem + ".py") or os.path.exists(stem + ".json")
    # every number of the catalog's config but the depth, which `published`
    # restores; the deployment; the assumptions
    assert cell["config_entry"]["reduced"] == CFG["reduced"] == [
        "num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 48}
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"], CFG["num_experts"],
            CFG["num_experts_per_tok"], CFG["moe_intermediate_size"],
            CFG["vocab_size"], CFG["num_hidden_layers"]) == (
        2048, 32, 4, 128, 128, 8, 768, 151936, 6)
    assert "v5e-8" in CFG["deployment"]
    for key in ("block_length", "schedule", "mask_token_id", "qk_norm",
                "rope"):
        assert key in CFG["assumed"], key
    assert {"dynamic_threshold", "sampled_requests",
            "prefix_pages_and_spec_k", "intermediate_size"} <= set(
        CFG["not_served"])
    assert set(CFG["limits"]) == {"served_logit_gap", "served_order_gap"}
    assert len(json.dumps(manifest)) < 64 * 1024


# ------------------------------------------------- the cell, tiny, on the CPU


def tiny_cell():
    """The cell's own files with the widths cut; every mechanism kept (two
    layers, 4 query heads over 2 key/value heads of 16, 8 experts top-2,
    blocks of 4, the mask token inside the vocabulary the traffic draws
    from). The limits are the tiny size's own."""
    import copy

    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_experts=8, num_experts_per_tok=2, vocab_size=256,
               mask_token_id=255,
               limits={"served_logit_gap": 0.05, "served_order_gap": 0.05})
    mix = copy.deepcopy(common.load_json(os.path.join(
        common.HERE, "traffic", "reason-closed.json")))
    mix.update(
        clients=4, pool=16, ramp_seconds=0.5, max_total=96, check_requests=4,
        prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.5,
                    "min": 5, "max": 48},
        new_tokens={"dist": "lognormal", "median": 16, "sigma": 0.5,
                    "min": 6, "max": 40},
        engine={"max_slots": 4, "page_size": 8, "max_pages_per_seq": 12,
                "max_prompt": 48, "prefix_pages": 0, "spec_k": 0})
    return {"name": "tiny.sdar", "chips": 1, "cfg": cfg, "mix": mix,
            "per_layer": [], "end_to_end": []}


def test_tiny_cell_is_correct_and_every_stand_in_is_not():
    import jax

    cell, seed = tiny_cell(), 2**31 + 17
    loop = common.module("loops", "serve_closed_loop")
    res = loop.run(cell, seed=seed, seconds=2.0, trace=False,
                   devs=jax.devices())
    assert res["checks"].correct, res["checks"].compared()
    assert res["failed"] == 0 and res["attempted"] > 0
    ctx = res["ctx"]
    per_pass = common.module("layer_metrics",
                             "diffusion_tokens_per_pass.serve").read(ctx)
    assert 0.5 < per_pass <= 0.8
    assert common.module("layer_metrics", "mfu").read(
        dict(ctx, kind="TPU v5 lite"), flops_per_s="serve_flops_per_s") > 0
    assert common.module("layer_metrics",
                         "moe_load_max_over_mean.serve").read(ctx) >= 1.0
    # no device trace on the CPU: the roofline's reader returns nothing
    assert common.module("layer_metrics",
                         "block_attn_roofline.serve").read(ctx) is None
    family = common.module("families", "sdar")
    tags = []
    for tag, readings, kw in family.stand_ins(cell["cfg"], cell["mix"], seed,
                                              ctx):
        checks = common.Checks()
        family.verify(cell["cfg"], cell["mix"], seed, readings, checks, **kw)
        tags.append(tag)
        assert checks.correct is False, (tag, checks.compared())
    assert tags == ["control_float8", "control_int8",
                    "fault_causal_in_block", "fault_kv_from_masked_pass",
                    "fault_prompt_tail_masked", "fault_wrong_group",
                    "fault_no_qk_norm", "fault_no_renorm"]
