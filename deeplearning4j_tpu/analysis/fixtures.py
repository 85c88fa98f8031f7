"""graftcheck fixture zoo — the graphs the gate's ``check`` stage verifies.

Two families:

* :func:`clean_fixtures` — representative clean graphs (the examples'
  SameDiff MLP, a symbolic-batch CNN, a symbolic-batch BERT-style encoder,
  a numpy-static shape chain, an ONNX-dialect import, and zoo networks).
  The committed ``check_baseline.json`` expects ZERO findings here; any
  finding is a regression in an op rule, an importer, or the checker.
* :func:`seeded_error_fixtures` — one graph per GC code with a planted
  bug, used by the suite (and docs/ANALYSIS.md) to pin each code's
  true-positive behavior.

Everything here is build-only: no jit, no execution — the fixtures stay
gate-cheap (<1s) even on CPU-only hosts.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import numpy as np

from deeplearning4j_tpu.autodiff.samediff import SameDiff, _Node


# ---------------------------------------------------------------------------
# clean graphs
# ---------------------------------------------------------------------------


def mlp_sym_batch() -> SameDiff:
    """The examples/samediff_training.py graph: symbolic-batch MLP."""
    r = np.random.RandomState(0)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(None, 8))
    labels = sd.placeholder("labels", shape=(None, 3))
    w0 = sd.var("w0", r.randn(8, 16).astype(np.float32) * 0.2)
    b0 = sd.var("b0", np.zeros(16, np.float32))
    w1 = sd.var("w1", r.randn(16, 3).astype(np.float32) * 0.2)
    h = sd.nn.relu(x @ w0 + b0)
    logits = h @ w1
    sd.loss.softmax_cross_entropy(logits, labels).rename("loss")
    logits.rename("logits")
    sd.graph_inputs, sd.graph_outputs = ["x", "labels"], ["logits", "loss"]
    return sd


def cnn_sym_batch() -> SameDiff:
    """Symbolic-batch conv/pool stack over the registry conv ops."""
    r = np.random.RandomState(1)
    sd = SameDiff()
    img = sd.placeholder("img", shape=(None, 28, 28, 1))
    w1 = sd.var("wc1", (r.randn(3, 3, 1, 8) * 0.1).astype(np.float32))
    w2 = sd.var("wc2", (r.randn(3, 3, 8, 16) * 0.1).astype(np.float32))
    c1 = sd.cnn.conv2d(img, w1, stride=1, padding="same")
    p1 = sd.cnn.max_pooling2d(sd.nn.relu(c1), kernel=2, stride=2)
    c2 = sd.cnn.conv2d(p1, w2, stride=1, padding="same")
    p2 = sd.cnn.avg_pooling2d(sd.nn.relu(c2), kernel=2, stride=2)
    p2.rename("features")
    sd.graph_inputs, sd.graph_outputs = ["img"], ["features"]
    return sd


def bert_encoder_sym_batch(layers: int = 2, seq: int = 128, d: int = 64,
                           ff: int = 128) -> SameDiff:
    """BERT-style encoder with a named symbolic batch dim — the
    ``placeholder(shape=(None, 128))`` acceptance graph. Attention is
    single-head (head splits need concrete reshape targets; the symbolic
    batch is what this fixture pins) with the full residual/layer-norm/
    gelu-FF block structure."""
    r = np.random.RandomState(2)
    sd = SameDiff()
    ids = sd.placeholder("ids", shape=(None, seq))
    mask = sd.placeholder("mask", shape=(None, seq))
    emb = sd.var("emb", (r.randn(512, d) * 0.02).astype(np.float32))
    pos = sd.var("pos", (r.randn(seq, d) * 0.02).astype(np.float32))
    x = sd.op("gather", emb, ids, axis=0) + pos

    scale = sd.constant("scale", np.float32(np.sqrt(d)))
    neg_big = sd.constant("neg_big", np.float32(-10000.0))
    one = sd.constant("one", np.float32(1.0))
    pen = (one - mask) * neg_big                      # (N, T)
    pen = sd._record("expand_dims", [pen], {"axis": 1})  # (N, 1, T)

    for i in range(layers):
        p = f"l{i}"
        wq = sd.var(f"{p}_wq", (r.randn(d, d) * 0.02).astype(np.float32))
        wk = sd.var(f"{p}_wk", (r.randn(d, d) * 0.02).astype(np.float32))
        wv = sd.var(f"{p}_wv", (r.randn(d, d) * 0.02).astype(np.float32))
        wo = sd.var(f"{p}_wo", (r.randn(d, d) * 0.02).astype(np.float32))
        g1 = sd.var(f"{p}_g1", np.ones(d, np.float32))
        b1 = sd.var(f"{p}_b1", np.zeros(d, np.float32))
        w_ff1 = sd.var(f"{p}_ff1", (r.randn(d, ff) * 0.02).astype(np.float32))
        w_ff2 = sd.var(f"{p}_ff2", (r.randn(ff, d) * 0.02).astype(np.float32))
        g2 = sd.var(f"{p}_g2", np.ones(d, np.float32))
        b2 = sd.var(f"{p}_b2", np.zeros(d, np.float32))

        q, k, v = x @ wq, x @ wk, x @ wv
        scores = (q @ k.transpose(0, 2, 1)) / scale
        probs = sd.nn.softmax(scores + pen, axis=-1)
        ctx = (probs @ v) @ wo
        x = sd.nn.layer_norm(x + ctx, g1, b1)
        h = sd.nn.gelu(x @ w_ff1)
        x = sd.nn.layer_norm(x + h @ w_ff2, g2, b2)

    cls_w = sd.var("cls_w", (r.randn(d, 2) * 0.02).astype(np.float32))
    sd.nn.softmax(x @ cls_w).rename("y")
    sd.graph_inputs, sd.graph_outputs = ["ids", "mask"], ["y"]
    return sd


def fused_graph_sym_batch(seq: int = 32, d: int = 64, heads: int = 4,
                          page: int = 8) -> SameDiff:
    """A graph built on the optimizer's fusion-target registry ops —
    ``dot_product_attention`` (incl. ``causal=``), ``fused_matmul_bias_act``
    and ``paged_decode_attention`` — with a named symbolic batch dim. The
    gate's ``check`` stage verifying this with ZERO findings proves the
    first-class analysis rules cover fused graphs natively: the
    ``jax.eval_shape`` probe cannot run over symbolic dims, so any rule
    regression surfaces as GC006 opacity or a phantom error here."""
    r = np.random.RandomState(5)
    hd = d // heads
    sd = SameDiff()
    q = sd.placeholder("q", shape=(None, heads, seq, hd))
    k = sd.placeholder("k", shape=(None, heads, seq, hd))
    v = sd.placeholder("v", shape=(None, heads, seq, hd))
    mask = sd.placeholder("mask", shape=(None, 1, 1, seq))
    att = sd.op("dot_product_attention", q, k, v, mask, scaled=True)
    catt = sd.op("dot_product_attention", q, k, v, scaled=True, causal=True)
    x = sd.placeholder("x", shape=(None, d))
    w1 = sd.var("w1", (r.randn(d, d) * 0.05).astype(np.float32))
    b1 = sd.var("b1", np.zeros(d, np.float32))
    h = sd.op("fused_matmul_bias_act", x, w1, b1, activation="gelu_exact")
    h.rename("h")
    att.rename("att")
    catt.rename("causal_att")
    # decode tier: one query token per slot against a block-paged KV cache
    dq = sd.placeholder("dq", shape=(None, heads, hd))
    kv = sd.var("kv_pages", (r.randn(2, 2, 6, page, d) * 0.1)
                .astype(np.float32))
    pt = sd.placeholder("page_table", shape=(None, 3), dtype=np.int32)
    sl = sd.placeholder("seq_lens", shape=(None,), dtype=np.int32)
    sd.op("paged_decode_attention", dq, kv, pt, sl, layer=1).rename("decoded")
    sd.graph_inputs = ["q", "k", "v", "mask", "x", "dq", "page_table",
                       "seq_lens"]
    sd.graph_outputs = ["att", "causal_att", "h", "decoded"]
    return sd


def tuned_kernels_sym_batch(d: int = 128) -> SameDiff:
    """The PR-9 kernel set as a symbolic-batch graph: ``fused_layer_norm``
    (+gelu epilogue), the int8 serving matmul (``quantize_int8`` →
    ``matmul_int8``) and a ``fused_updater_step`` leaf. Verifying this with
    ZERO findings proves the first-class rules cover the new registry ops
    natively — no ``jax.eval_shape`` probe fallback (which cannot run over
    the symbolic batch dim)."""
    r = np.random.RandomState(9)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(None, d))
    g = sd.var("ln_g", np.ones(d, np.float32))
    b = sd.var("ln_b", np.zeros(d, np.float32))
    h = sd.op("fused_layer_norm", x, g, b, axis=-1, eps=1e-5,
              activation="gelu")
    w = sd.var("w", (r.randn(d, d) * d ** -0.5).astype(np.float32))
    # the keepdims (1, N) scale straight out of quantize: the matmul_int8
    # rule and impls both accept it — no reshape glue needed
    wq, ws = sd.op("quantize_int8", w, axis=0, n_out=2)
    sd.op("matmul_int8", h, wq, ws).rename("y")
    # one fused optimizer leaf (concrete shapes — updater state has no
    # batch dim); Adam: state rides sorted as (m, v)
    p = sd.var("p", (r.randn(d) * 0.1).astype(np.float32))
    gr = sd.var("grad", (r.randn(d) * 0.01).astype(np.float32))
    m0 = sd.var("m0", np.zeros(d, np.float32))
    v0 = sd.var("v0", np.zeros(d, np.float32))
    lr = sd.constant(np.float32(1e-3))
    step = sd.constant(np.float32(0.0))
    new_p, _m1, _v1 = sd.op("fused_updater_step", p, gr, lr, step, m0, v0,
                            kind="Adam", n_out=3)
    new_p.rename("new_p")
    sd.graph_inputs, sd.graph_outputs = ["x"], ["y", "new_p"]
    return sd


def shape_chain() -> SameDiff:
    """numpy-static shape arithmetic: shape_of → unstack → stack →
    reshape_dynamic — the constant-env surface."""
    sd = SameDiff()
    x = sd.var("x", np.ones((6, 4), np.float32))
    s = sd.op("shape_of", x)
    a, b = sd.op("unstack", s, n_out=2)
    tgt = sd.op("stack", b, a)
    sd.op("reshape_dynamic", x, tgt).rename("y")
    sd.graph_inputs, sd.graph_outputs = [], ["y"]
    return sd


def onnx_mini_import() -> SameDiff:
    """A small ONNX-dialect graph (symbolic batch) lowered through the
    real importer mappers + IR walker — exercises the full
    import-then-check path without protobuf bytes."""
    from deeplearning4j_tpu.imports.ir import IRGraph, IRNode
    from deeplearning4j_tpu.imports.onnx_import import OnnxImporter

    r = np.random.RandomState(3)
    init = {
        "w0": (r.randn(8, 16) * 0.2).astype(np.float32),
        "b0": np.zeros(16, np.float32),
        "w1": (r.randn(16, 3) * 0.2).astype(np.float32),
    }
    nodes = [
        IRNode("mm0", "MatMul", ["x", "w0"], ["mm0"]),
        IRNode("a0", "Add", ["mm0", "b0"], ["a0"]),
        IRNode("r0", "Relu", ["a0"], ["r0"]),
        IRNode("mm1", "MatMul", ["r0", "w1"], ["mm1"]),
        IRNode("y", "Softmax", ["mm1"], ["y"], attrs={"axis": -1}),
    ]
    ir = IRGraph(nodes=nodes, initializers=init,
                 inputs=[("x", (None, 8))], outputs=["y"], name="onnx")
    return OnnxImporter().run_import(ir)


def zoo_networks() -> List[Tuple[str, Any]]:
    """Layer-level zoo graphs for check_network (built, not trained)."""
    from deeplearning4j_tpu import models, nn
    from deeplearning4j_tpu.nn.graph import (
        ComputationGraph, ElementWiseVertex, graph_builder)

    lenet = models.LeNet(num_classes=10)
    residual = ComputationGraph(
        graph_builder().seed(0)
        .add_inputs("in")
        .set_input_types(**{"in": nn.InputType.feed_forward(6)})
        .add_layer("d", nn.DenseLayer(n_out=6, activation="relu"), "in")
        .add_vertex("add", ElementWiseVertex(op="add"), "d", "in")
        .add_layer("out", nn.OutputLayer(n_out=2, activation="softmax",
                                         loss="mcxent"), "add")
        .set_outputs("out").build())
    return [("net/lenet", lenet), ("net/residual_graph", residual)]


def clean_fixtures() -> List[Tuple[str, Any]]:
    """(name, SameDiff-or-network) — the gate's zero-findings surface."""
    out: List[Tuple[str, Any]] = [
        ("zoo/mlp_sym_batch", mlp_sym_batch()),
        ("zoo/cnn_sym_batch", cnn_sym_batch()),
        ("zoo/bert_encoder_sym_batch", bert_encoder_sym_batch()),
        ("zoo/fused_graph_sym_batch", fused_graph_sym_batch()),
        ("zoo/tuned_kernels_sym_batch", tuned_kernels_sym_batch()),
        ("zoo/shape_chain", shape_chain()),
        ("onnx/mini_mlp", onnx_mini_import()),
    ]
    out.extend(zoo_networks())
    return out


# ---------------------------------------------------------------------------
# seeded errors — one per GC code (docs/ANALYSIS.md examples)
# ---------------------------------------------------------------------------


def seeded_error_fixtures() -> List[Tuple[str, str, SameDiff]]:
    """(expected_code, name, graph) triples. Planted with sd internals
    where the public API already refuses the mistake (the checker's job is
    graphs that arrive broken — deserialization, importer bugs)."""
    out: List[Tuple[str, str, SameDiff]] = []

    sd = SameDiff()
    x = sd.placeholder("x", (2, 3))
    sd._record("transpose", [x], {"axes": (0, 1, 2)})
    out.append(("GC001", "seeded/rank_mismatch", sd))

    sd = SameDiff()
    a = sd.placeholder("a", (2, 3))
    b = sd.placeholder("b", (4, 5))
    a + b
    out.append(("GC002", "seeded/broadcast_failure", sd))

    sd = SameDiff()
    a = sd.var("i32", np.ones(3, np.int32))
    b = sd.var("u32", np.ones(3, np.uint32))
    sd._record("add", [a, b])
    out.append(("GC003", "seeded/promotion_surprise", sd))

    sd = SameDiff()
    sd.placeholder("x", (3,))
    sd._nodes.append(_Node("add", ["x", "ghost"], {}, ["dangling_out"]))
    out.append(("GC004", "seeded/dangling_input", sd))

    sd = SameDiff()
    x = sd.placeholder("x", (4, 3))
    x.reshape(5, 3)
    out.append(("GC005", "seeded/reshape_count", sd))

    sd = SameDiff()
    x = sd.placeholder("x", (None, 8))
    sd.op("top_k", x, k=2, n_out=2)
    out.append(("GC006", "seeded/unknown_op", sd))

    return out
