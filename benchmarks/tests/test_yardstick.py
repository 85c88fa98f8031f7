"""The yardstick's arithmetic: trace reduction, counts, percentiles, traffic,
peaks."""

import json
import os

import numpy as np
import pytest

import common
import trace_reduce
import traffic_gen
from conftest import HERE


def test_reduction_on_recorded_trace():
    trace = json.load(open(os.path.join(
        HERE, "data", "bert_call_boundary_trace.json")))
    r = trace_reduce.reduce_trace(trace, gap_default="between_calls")
    assert r["window_s"] == pytest.approx(0.007)
    # 7 ms around the boundary of two calls: the device waits for the host
    assert 0 < r["busy_s"] < 0.001
    assert r["idle_gaps"][0][0] == "bench_train_call"
    assert r["longest_gaps"][0][1] == pytest.approx(0.002805489)
    sec, n = trace_reduce.matching(
        r, ('custom_call_target="tpu_custom_call"', "%step_fn"))
    assert n == 4 and sec == pytest.approx(3.8675e-05)
    assert trace_reduce.matching(r, ("no_such_kernel",)) is None
    assert all(len(name) <= 120 for name, _ in r["device_ops"])


def test_reduction_self_time_union_and_labels():
    dev = [("while", 0, 100), ("a", 10, 30), ("b", 50, 40), ("c", 200, 50),
           ("c", 300, 20)]
    trace = {"devices": {"/device:TPU:0": dev},
             "host": [("bench_window", 0, 400), ("bench_wait", 90, 120),
                      ("bench_inner", 100, 50)]}
    r = trace_reduce.reduce_trace(trace)
    assert r["busy_s"] == pytest.approx((100 + 70) / 1e9)
    assert r["window_s"] == pytest.approx(400 / 1e9)
    secs = {k: round(v * 1e9) for k, v in r["op_seconds"].items()}
    assert secs == {"while": 30, "a": 30, "b": 40, "c": 70}
    assert r["op_counts"]["c"] == 2
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {trace_reduce.SHORT_GAP_LABEL}  # all under 20 us
    assert trace_reduce.label_at(trace["host"], 100, 200, "x") == "bench_wait"
    assert trace_reduce.label_at(trace["host"], 100, 140, "x") == "bench_inner"
    assert trace_reduce.label_at(trace["host"], 390, 400, "x") == "x"


def test_reduction_of_nothing_reads_nothing():
    r = trace_reduce.reduce_trace({"devices": {}, "host": []})
    assert r["busy_s"] == 0.0
    idle = common.module("layer_metrics", "device_idle")
    assert idle.read({"trace": r}) is None


def test_bert_operations_against_hand_sum():
    counts = common.module("counts", "bert")
    cfg = common.load_json(common.HERE + "/configs/bert-base.json")
    # by hand: 12 x (8*768^2 + 4*512*768 + 4*768*3072) + 2*768^2 + 2*768*30522
    assert counts.forward_flops_per_token(cfg, 512) == 12 * 15728640 + 48061440
    per_sample = counts.train_flops_per_sample(cfg, {"seq": 512})
    assert per_sample == pytest.approx(0.7104e9 * 512, rel=1e-3)
    import jax
    ref = common.module("reference", "bert")
    leaves = jax.tree.leaves(ref.tree_spec(cfg),
                             is_leaf=lambda x: hasattr(x, "shape"))
    assert counts.param_count(cfg) == sum(int(np.prod(l.shape)) for l in leaves)


def test_gpt_operations_against_hand_sum():
    counts = common.module("counts", "gpt")
    cfg = common.load_json(common.HERE + "/configs/gpt2-small.json")
    dense = 12 * (8 * 768 ** 2 + 4 * 768 * 3072)
    head = 2 * 768 * 50257
    assert counts.decode_flops(cfg, 100) == dense + 12 * 4 * 768 * 100 + head
    assert counts.prompt_flops(cfg, 2) == 2 * dense + 12 * 4 * 768 * 3 + head


def test_flash_attention_counts_against_hand_sums():
    counts = common.module("counts", "flash_attention")
    cfg = common.load_json(common.HERE + "/configs/bert-base.json")
    flops, moved = counts.per_sample(cfg, 512)
    # a head: 7 matmuls of 512 x 512 x 64, 2 operations a multiply-add
    assert flops == 12 * 12 * 7 * 2 * 512 * 512 * 64
    # 12 tensors of 512 x 768 in bf16 a layer
    assert moved == 12 * 12 * 512 * 768 * 2
    peaks = common.peaks_of("TPU v5 lite")
    seconds, bound = counts.least_seconds(cfg, 512, 32, peaks)
    assert bound == "operations"
    assert seconds == pytest.approx(32 * flops / 197e12)


def test_percentile_is_over_all_samples():
    xs = list(range(1, 101))
    assert common.percentile(xs, 95) == pytest.approx(95.05)
    assert common.percentile(xs, 50) == pytest.approx(50.5)
    assert common.percentile([3.0], 95) == 3.0
    assert common.percentile(xs + [1e6], 100) == 1e6  # no sample is dropped
    with pytest.raises(ValueError):
        common.percentile([], 95)


def test_traffic_reproduces_from_a_seed_and_keeps_its_sizes():
    mix = common.load_json(common.HERE + "/traffic/chat-closed.json")
    flat = lambda hands: [r for hand in hands for r in hand]  # noqa: E731
    a = traffic_gen.client_sequences(mix, 2**31 + 11, 50257)
    b = traffic_gen.client_sequences(mix, 2**31 + 11, 50257)
    c = traffic_gen.client_sequences(mix, 5, 50257)
    assert len(a) == 32 and sum(map(len, a)) == 256
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(flat(a), flat(b)))
    # every seed has the same hands of sizes, dealt to other clients, and
    # other token ids
    hand = lambda h: tuple((len(r["prompt"]), r["max_new_tokens"])  # noqa: E731
                           for r in h)
    assert sorted(map(hand, a)) == sorted(map(hand, c))
    assert list(map(hand, a)) != list(map(hand, c))
    assert not np.array_equal(flat(a)[0]["prompt"][:8], flat(c)[0]["prompt"][:8])
    lens = np.array([len(r["prompt"]) for r in flat(a)])
    new = np.array([r["max_new_tokens"] for r in flat(a)])
    assert lens.min() >= 64 and lens.max() <= 896
    assert new.min() >= 16 and new.max() <= 128
    assert (lens + new).max() <= 1024
    assert abs(np.median(lens) - 512) <= 8 and abs(np.median(new) - 64) <= 2


def test_unknown_device_kind_raises():
    assert common.peaks_of("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(common.BenchError):
        common.peaks_of("TPU v99")


def test_a_reader_that_finds_nothing_returns_nothing():
    cell = {"per_layer": [{"name": "device_idle_pct.train"},
                          {"name": "paged_attn_roofline.serve"},
                          {"name": "decode_step_ms.serve"},
                          {"name": "helper_tpu_dispatches.serve"}]}
    vals = common.read_layer_metrics(cell, {"trace": None, "spans": [],
                                            "dispatch": {}})
    assert vals == {k["name"]: None for k in cell["per_layer"]}
    assert common.select_metrics(
        [{"name": k, "unit": "%"} for k in vals], vals) == {}
