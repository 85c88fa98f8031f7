"""chip_smoke.py, rehearsed: its phases run in-process at tiny sizes on the
CPU (sizes are arguments of the phase functions), and without a TPU its
``main`` refuses — non-zero, ``"ok": false`` as the last line."""

import importlib.util
import json
import os

import pytest

from deeplearning4j_tpu import nn
from deeplearning4j_tpu.models.bert import BertConfig
from deeplearning4j_tpu.models.gpt import GptConfig
from deeplearning4j_tpu.nn.graph import ComputationGraph, graph_builder
from deeplearning4j_tpu.nn.updater import Adam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(last)
    assert rec["ok"] is False
    assert rec["device"]["platform"] == "cpu"


def test_bert_phase_tiny(smoke):
    # bf16 params round a 2e-5 step away at this size: a larger one here
    # (every matrix 32 x 32: eager init compiles once per distinct shape)
    cfg = BertConfig.tiny(layers=1, hidden=32, heads=2, intermediate=32,
                          vocab_size=32, max_position=32)
    rep, _ = smoke.phase_bert(cfg=cfg, batch=2, seq=8, steps=2,
                              updater=Adam(learning_rate=1e-2))
    assert len(rep["losses"]) == 4 and rep["losses"][-1] < rep["losses"][0]
    assert any(k.startswith("dot_product_attention/") for k in rep["dispatch"])
    assert any(k.startswith("fused_updater_step/") for k in rep["dispatch"])


def shallow_graph(image, classes, seed):
    """Same entry points as ResNet-50 (ComputationGraph, conv + BN,
    Nesterovs, mixed dtype), three layers deep: compiles in a second."""
    b = (graph_builder().seed(seed).weight_init("relu").dtype("mixed")
         .updater(nn.Nesterovs(learning_rate=0.1, momentum=0.9))
         .add_inputs("input")
         .set_input_types(input=nn.InputType.convolutional(image, image, 3)))
    b.add_layer("conv", nn.ConvolutionLayer(
        n_out=8, kernel=(3, 3), convolution_mode="same",
        activation="identity", has_bias=False), "input")
    b.add_layer("bn", nn.BatchNormalization(activation="relu"), "conv")
    b.add_layer("gap", nn.GlobalPoolingLayer(pooling_type="avg"), "bn")
    b.add_layer("fc", nn.OutputLayer(n_out=classes, activation="softmax",
                                     loss="mcxent"), "gap")
    b.set_outputs("fc")
    return ComputationGraph(b.build()).init()


def test_resnet_phase_tiny(smoke):
    rep, _ = smoke.phase_resnet(make_net=shallow_graph, image=8, classes=4,
                                batch=2, steps=1)
    assert len(rep["losses"]) == 2
    assert any(k.startswith("fused_updater_step/") for k in rep["dispatch"])


def test_gpt_phase_tiny(smoke):
    rep, compiled_text = smoke.phase_gpt(
        cfg=GptConfig.tiny(layers=1, hidden=32, heads=2, intermediate=32,
                           vocab_size=32, max_position=32),
        slots=4, page_size=8, pages_per_seq=4, max_prompt=16,
        prompt_lens=(12, 5, 9), new_tokens=4)
    text = compiled_text()  # the decode step, lowered again
    assert "HloModule" in text
    assert rep["kv_pool"] == [1, 2, 17, 8, 32]  # heads merged: 2 x 16
    assert smoke.pool_copies(text, rep["kv_pool"]) == 0
    assert smoke.pool_copies(
        "%copy.10 = f32[1,2,17,8,32]{4,3,2,1,0:T(8,128)} copy(%p)",
        rep["kv_pool"]) == 1
    assert rep["requests"] == 4 and rep["bank_tokens"] == 12
    assert rep["finish_reasons"] == ["length"]
    assert rep["first_token"] in rep["reference_top2"]
    assert any(k.startswith("paged_decode_attention/")
               for k in rep["dispatch"])


def test_dp_phase_tiny_on_four_virtual_devices(smoke):
    # one step: at batch 8 the second step of lr 0.1 already diverges, and
    # a diverging run amplifies the reduction-order difference past any
    # tolerance
    rep = smoke.phase_dp(chips=4, make_net=shallow_graph, image=8,
                         classes=4, batch=8, steps=1)
    assert rep["all_reduce_ops"] > 0 and rep["batch_shard"] == "[2,8,8,3]"
    assert len(rep["mesh_losses"]) == 1


def test_chip_evidence_rejects_a_phase_that_ran_generic(smoke):
    rep = {"phase": "serve/gpt2", "mosaic_calls": 0, "platforms": ["cpu"],
           "dispatch": {"paged_decode_attention/generic/no_helper": 12}}
    with pytest.raises(smoke.CheckFailed, match="never dispatched impl=tpu"):
        smoke.check_chip_evidence(rep)
    rep = {"phase": "serve/gpt2", "mosaic_calls": 12, "platforms": ["tpu"],
           "dispatch": {"paged_decode_attention/tpu/usable": 12},
           "kv_pool": [12, 2, 1041, 16, 768], "pool_copies": 2}
    with pytest.raises(smoke.CheckFailed, match="copies the KV pool"):
        smoke.check_chip_evidence(rep)
