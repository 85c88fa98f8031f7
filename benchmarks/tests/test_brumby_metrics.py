"""The ``brumby`` family's counts and the two readers PR 34 adds, each on a
small hand-recorded trace (plain form, names as the v5e's trace prints them)
and hand-recorded spans; then the cell's own files at a tiny size on the
CPU."""

import json
import os

import pytest

import common
import trace_reduce

CFG = common.load_json(os.path.join(common.HERE, "configs",
                                    "brumby-14b-base.json"))
CELL = {"cfg": CFG, "mix": {}}
NAME = "brumby-14b-base.reason-closed-32"

KERNEL = ('%retention_decode.{n} = (f32[32,5,8,65,128,128]{{5,4,3,2,1,0:T(8,'
          '128)}}, f32[32,5,8,65,128]{{4,3,2,1,0:T(8,128)}}, f32[32,8,5,128]'
          '{{3,2,1,0:T(8,128)}}) custom-call(%reshape.37, %broadcast.1, '
          '%fusion.2, %fusion.1, %st.1, %copy.141), '
          'custom_call_target="tpu_custom_call"')
# another Mosaic call, and a fusion inside the kernel's named scope with the
# pool's shape: neither may be read as the retention kernel
OTHER = ('%grouped_gate_up.10 = f32[512,1024]{1,0:T(8,128)} custom-call(%a, '
         '%b), custom_call_target="tpu_custom_call"')
POOL_SHAPED = ('%multiply_multiply_fusion = f32[32,8,5,65,128]{4,3,2,1,0:T(8,'
               '128)} fusion(%p), kind=kLoop, metadata={op_name="jit(decode)/'
               'retention_decode/mul"}')


def traced(events):
    trace = {"devices": {"/device:TPU:0": events},
             "host": [("bench_window", 0, 1_000_000_000)]}
    return trace_reduce.reduce_trace(trace)


def span(name, **args):
    return {"name": name, "start": 0.0, "seconds": 0.01, "args": args}


def test_counts_follow_the_issues_arithmetic():
    counts = common.module("counts", "brumby")
    # ISSUE 34 section 2: W_q, W_o 26.21 M each, W_k, W_v 5.24 M each, gate
    # 0.04 M, feed-forward 267.39 M: a layer 330.3 M = 0.661 GB
    assert counts.retention_params(CFG) == (
        2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8) == 62_955_528
    assert 3 * 5120 * 17408 == 267_386_880
    assert counts.layer_params(CFG) == pytest.approx(330.3e6, rel=2e-4)
    assert 2 * counts.layer_params(CFG) == pytest.approx(0.661e9, rel=2e-3)
    # embedding and head 777.9 M each: 3.11 GB
    assert counts.outer_params(CFG) == 2 * 151936 * 5120
    assert counts.outer_params(CFG) / 2 == pytest.approx(777.9e6, rel=1e-4)
    # this chip: 3.21 B parameters, 6.41 GB; the whole model 14.77 B
    here = counts.outer_params(CFG) + 5 * counts.layer_params(CFG)
    assert here == pytest.approx(3.21e9, rel=2e-3)
    assert 2 * here == pytest.approx(6.41e9, rel=2e-3)
    whole = counts.outer_params(CFG) + 40 * counts.layer_params(CFG)
    assert whole == pytest.approx(14.77e9, rel=1e-3)
    # a slot's state: 5 layers x 8 heads x (8256 x 128 + 8256) x 4 bytes =
    # 170.4 MB; 32 slots 5.45 GB
    assert counts.symmetric_rows(CFG) == 128 * 129 // 2 == 8256
    assert counts.slot_state_bytes(CFG) == 5 * 8 * (8256 * 128 + 8256) * 4
    assert counts.slot_state_bytes(CFG) == pytest.approx(170.4e6, rel=1e-3)
    assert 32 * counts.slot_state_bytes(CFG) == pytest.approx(5.45e9,
                                                              rel=1e-3)
    # a decode step moves the state once in and once out: 10.9 GB of the
    # 15.8 GB with the weights (3.30 GB of layers + 1.56 GB of head)
    step = counts.state_bytes_per_step(CFG, 32)
    assert step == pytest.approx(10.9e9, rel=2e-3)
    assert step == 32 * (2 * counts.slot_state_bytes(CFG)
                         + 5 * ((40 + 16) * 128 + 8) * 4)
    weights = 2 * 5 * counts.layer_params(CFG) + counts.outer_params(CFG)
    assert weights == pytest.approx(4.86e9, rel=2e-3)
    assert (step + weights) == pytest.approx(15.8e9, rel=3e-3)
    # operations a generated token: 4.86 GFLOP of matrices + 0.51 of
    # retention (40 reads and 8 updates of 8256 x 128 a layer), at every
    # context length
    assert counts.retention_decode_flops(CFG) == 2 * 5 * 48 * 8256 * 128
    assert counts.retention_decode_flops(CFG) == pytest.approx(0.51e9,
                                                               rel=1e-2)
    assert counts.decode_flops(CFG, 1) == counts.decode_flops(CFG, 1500)
    assert counts.decode_flops(CFG) == pytest.approx(
        2 * (5 * (counts.layer_params(CFG) - 8) + 151936 * 5120)
        + counts.retention_decode_flops(CFG))
    assert counts.decode_flops(CFG) == pytest.approx(4.86e9 + 0.51e9,
                                                     rel=3e-3)
    # a prompt: the matrices a token, the quadratic form (scores and
    # weighted values: 2 x 128 products a head a pair) and the state's one
    # product, 8256 x 129 a key/value head a position
    form = 2 * 40 * 256 * 100 * 101 / 2
    build = 2 * 8 * 8256 * 129 * 100
    assert counts.prompt_flops(CFG, 100) == pytest.approx(
        100 * 2 * 5 * (counts.layer_params(CFG) - 8) + 5 * (form + build)
        + 2 * 5120 * 151936)


def test_mfu_reads_the_family_counts():
    read = common.module("layer_metrics", "mfu").read
    counts = common.module("counts", "brumby")
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "chips": 1,
           "window": (0.0, 2.0), "tokens": [(40, [0.5, 1.0, 1.5, 2.5])]}
    want = (counts.prompt_flops(CFG, 40) + 2 * counts.decode_flops(CFG)) / 2.0
    assert read(ctx, flops_per_s="serve_flops_per_s") == pytest.approx(
        100.0 * want / 197e12)
    assert read(dict(ctx, tokens=[]), flops_per_s="serve_flops_per_s") is None


def test_retention_decode_roofline_finds_the_kernel_by_its_name():
    read = common.module("layer_metrics",
                         "retention_decode_roofline.serve").read
    counts = common.module("counts", "brumby")
    # two decode steps: ten events of the kernel (one a layer), 3.4 ms each
    events = [(KERNEL.format(n=8 + i), 1000 + i * 4_000_000, 3_400_000)
              for i in range(10)]
    tr = traced(events + [(OTHER, 50_000_000, 900_000),
                          (POOL_SHAPED, 52_000_000, 900_000)])
    spans = [span("serving_decode", slots=32, ret_den_min=3.0),
             span("serving_decode", slots=30, ret_den_min=2.0),
             span("serving_prefill", prompt_len=100)]
    ctx = {"cell": CELL, "kind": "TPU v5 lite", "trace": tr, "spans": spans}
    need = 2 * counts.state_bytes_per_step(CFG, 31.0)
    assert read(ctx) == pytest.approx(100.0 * (need / 819e9) / 34e-3)
    assert 70 < read(ctx) < 80
    # nothing of that name: nothing returned, never 0 and never another
    # kernel's or an operand's shape; no trace, no decode span: nothing
    assert read(dict(ctx, trace=traced([(OTHER, 0, 5000),
                                        (POOL_SHAPED, 9000, 500)]))) is None
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, spans=[])) is None
    # another family's cell traced with these events (it has none, but a
    # reader must not raise): its counts have no state bytes
    xing = {"cfg": common.load_json(os.path.join(
        common.HERE, "configs", "xing4.0-29b-a4b.json")), "mix": {}}
    assert read(dict(ctx, cell=xing)) is None


def test_retention_den_min_reads_the_decode_spans():
    read = common.module("layer_metrics", "retention_den_min.serve").read
    spans = [
        span("serving_decode", slots=32, ret_den_min=41.0,
             ret_state_absmax=7.0, ret_decay_mean=0.994),
        span("serving_decode", slots=32, ret_den_min=0.37,
             ret_state_absmax=9.0, ret_decay_mean=0.995),
        span("serving_decode", slots=32, ret_den_min=12.5,
             ret_state_absmax=8.0, ret_decay_mean=0.995),
        # a prefill's arguments are not a decode step's
        span("serving_prefill", prompt_len=200, ret_den_min=0.001,
             ret_state_absmax=1.0, ret_decay_mean=0.99),
        span("serving_step"),
    ]
    assert read({"cell": CELL, "spans": spans}) == pytest.approx(0.37)
    # a program without a retention layer (the other cells' spans; the
    # parent's): nothing
    plain = [span("serving_decode", slots=32, moe_held=5)]
    assert read({"cell": CELL, "spans": plain}) is None
    assert read({"cell": CELL, "spans": []}) is None


def test_the_manifest_lists_the_cell_under_every_metric_it_reports():
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.load_cell(NAME)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {
        "decode_step_ms.serve", "itl_p95_ms.serve", "slot_occupancy_pct.serve",
        "helper_tpu_dispatches.serve", "mfu_pct.serve",
        "device_idle_pct.serve", "step_host_ms.serve",
        "programs_compiled.serve", "retention_decode_roofline.serve",
        "retention_den_min.serve"}
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    assert cell["chips"] == 1 and cell["traffic"] == "reason-closed-32"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for new in ("retention_decode_roofline.serve", "retention_den_min.serve"):
        assert by_name[new]["workloads"] == [NAME]
        assert by_name[new]["moves"] == "serve_tokens_per_s"
    assert by_name["retention_decode_roofline.serve"]["layer"] == "kernels"
    assert by_name["retention_den_min.serve"]["layer"] == "retention state"
    # the new entries stand last in their lists
    assert manifest["workloads"][-1]["name"] == NAME
    assert manifest["configs"][-1]["name"] == "brumby-14b-base"
    assert [m["name"] for m in manifest["per_layer"][-2:]] == [
        "retention_decode_roofline.serve", "retention_den_min.serve"]
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if NAME in m.get("workloads", []):
            assert m["workloads"][-1] == NAME
    # every reader of the cell is a file found by the metric's name
    for name in names:
        stem = os.path.join(common.HERE, "layer_metrics", name)
        assert os.path.exists(stem + ".py") or os.path.exists(stem + ".json")
    # the configuration: every number of the source's config but the depth,
    # which `published` restores; the deployment; the assumptions
    entry = cell["config_entry"]
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 40}
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"],
            CFG["intermediate_size"], CFG["vocab_size"], CFG["rope_theta"],
            CFG["tie_word_embeddings"], CFG["num_hidden_layers"]) == (
        5120, 40, 8, 128, 17408, 151936, 1000000, False, 5)
    assert "v5e-8" in CFG["deployment"] and "eight pipeline stages" in CFG[
        "deployment"]
    for key in ("retention_degree", "gate", "normaliser", "scale",
                "state_dtype", "qk_norm_and_rope", "grouped_heads"):
        assert key in CFG["assumed"], key
    assert "not_given" in CFG["assumed"]["not_given"]
    assert (CFG["retention_degree"], CFG["state_dtype"]) == (2, "float32")
    # the mix: reason-closed's lengths on a bank of 32
    mix, old = cell["mix"], common.load_json(os.path.join(
        common.HERE, "traffic", "reason-closed.json"))
    assert (mix["clients"], mix["pool"], mix["engine"]["max_slots"]) == (
        32, 256, 32)
    for key in ("prompt_len", "new_tokens", "max_total", "temperature",
                "ramp_seconds", "check_requests", "loop", "eos_token"):
        assert mix[key] == old[key], key
    assert {k: v for k, v in mix["engine"].items() if k != "max_slots"} == {
        k: v for k, v in old["engine"].items() if k != "max_slots"}
    assert len(json.dumps(manifest)) < 64 * 1024


# ------------------------------------------------- the cell, tiny, on the CPU


def tiny_cell():
    """The cell's own files with the widths cut; every mechanism kept (two
    layers, 4 query heads over 2 key/value heads of 16, the gate). The limit
    is the tiny size's own."""
    import copy

    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=256, limits={"served_logit_gap": 0.05})
    cfg["init"]["gate_bias"] = 4.0
    mix = copy.deepcopy(common.load_json(os.path.join(
        common.HERE, "traffic", "reason-closed-32.json")))
    mix.update(
        clients=4, pool=16, ramp_seconds=0.5, max_total=128, check_requests=6,
        prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.5,
                    "min": 8, "max": 64},
        new_tokens={"dist": "lognormal", "median": 24, "sigma": 0.5,
                    "min": 8, "max": 48},
        engine={"max_slots": 4, "page_size": 8, "max_pages_per_seq": 16,
                "max_prompt": 64, "prefix_pages": 0, "spec_k": 0})
    return {"name": "tiny.brumby", "chips": 1, "cfg": cfg, "mix": mix,
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
    den = common.module("layer_metrics", "retention_den_min.serve").read(ctx)
    assert den > 0
    assert common.module("layer_metrics", "programs_compiled").read(ctx) > 0
    assert common.module("layer_metrics", "mfu").read(
        dict(ctx, kind="TPU v5 lite"), flops_per_s="serve_flops_per_s") > 0
    # no device trace on the CPU: the roofline's reader returns nothing
    assert common.module(
        "layer_metrics", "retention_decode_roofline.serve").read(ctx) is None
    decode = [s for s in ctx["spans"] if s["name"] == "serving_decode"]
    assert decode and all(
        {"ret_den_min", "ret_state_absmax", "ret_decay_mean"}
        <= set(s["args"]) for s in decode)
    family = common.module("families", "brumby")
    tags = []
    for tag, readings, kw in family.stand_ins(cell["cfg"], cell["mix"], seed,
                                              ctx):
        checks = common.Checks()
        family.verify(cell["cfg"], cell["mix"], seed, readings, checks, **kw)
        tags.append(tag)
        if tag == "control_bf16_state":
            # at 16-wide heads and sequences of some 50 tokens a bfloat16
            # state lies inside the bfloat16 program's own rounding (it
            # flips no token of 256 words); the chip's calibration holds it
            # at the cell's size, tests/test_brumby.py on the logits
            continue
        assert checks.correct is False, (tag, checks.compared())
    assert tags == ["control_bf16_state", "control_float8", "control_int8",
                    "fault_no_gate", "fault_no_normaliser", "fault_degree_1",
                    "fault_no_prompt_state", "fault_wrong_group"]
