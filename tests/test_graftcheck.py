"""graftcheck (analysis/ — docs/ANALYSIS.md): per-code seeded fixtures,
symbolic-dim soundness, the constant env, importer/validate wiring,
check_network, and the CLI/baseline contract."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import (
    AVal, Dim, GC_CODES, GraphCheckError, check_network, check_samediff)
from deeplearning4j_tpu.analysis import fixtures
from deeplearning4j_tpu.analysis.broadcast import (
    BroadcastError, broadcast_shapes, promotion_surprise)
from deeplearning4j_tpu.autodiff.samediff import SameDiff, _Node

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the six GC codes: seeded true positives with provenance
# ---------------------------------------------------------------------------


class TestSeededCodes:
    @pytest.mark.parametrize(
        "code,name,graph",
        fixtures.seeded_error_fixtures(),
        ids=[c for c, _n, _g in fixtures.seeded_error_fixtures()])
    def test_seeded_fixture_flags_its_code(self, code, name, graph):
        report = check_samediff(graph, graph_name=name)
        hit = [f for f in report.findings if f.rule == code]
        assert hit, (f"{code} not flagged on {name}; got "
                     f"{[f.render() for f in report.findings]}")
        # provenance: op + node name in the message, graph name as path,
        # node position as line
        f = hit[0]
        assert f.path == name
        assert "op " in f.message and "node '" in f.message
        assert f.line >= 1
        # severity matches the catalog
        assert f.severity == GC_CODES[code][0]

    def test_error_codes_raise_warnings_do_not(self):
        for code, name, graph in fixtures.seeded_error_fixtures():
            report = check_samediff(graph, graph_name=name)
            if GC_CODES[code][0] == "error":
                with pytest.raises(GraphCheckError):
                    report.raise_on_errors()
            else:
                report.raise_on_errors()  # warnings never raise


class TestCleanFixtures:
    @pytest.mark.parametrize(
        "name,graph", fixtures.clean_fixtures(),
        ids=[n for n, _g in fixtures.clean_fixtures()])
    def test_zero_findings(self, name, graph):
        if isinstance(graph, SameDiff):
            report = check_samediff(graph, graph_name=name)
        else:
            report = check_network(graph, graph_name=name)
        assert report.findings == [], "\n".join(
            f.render() for f in report.findings)


# ---------------------------------------------------------------------------
# symbolic dims + broadcasting soundness
# ---------------------------------------------------------------------------


class TestSymbolicDims:
    def test_named_batch_dim_flows_through(self):
        sd = SameDiff()
        x = sd.placeholder("x", (None, 128))
        w = sd.var("w", np.zeros((128, 64), np.float32))
        y = sd.nn.relu(x @ w)
        report = check_samediff(sd)
        assert report.findings == []
        aval = report.avals[y.name]
        assert aval.shape == (Dim("x.0"), 64)
        assert aval.dtype == np.dtype(np.float32)

    def test_same_symbol_unifies_across_operands(self):
        # (N, 128) + (N, 128) from the SAME placeholder-rooted chain: the
        # named dim survives (not degraded to unknown)
        sd = SameDiff()
        x = sd.placeholder("x", (None, 16))
        y = sd.math.tanh(x) + sd.math.exp(x)
        report = check_samediff(sd)
        assert report.findings == []
        assert report.avals[y.name].shape == (Dim("x.0"), 16)

    def test_symbolic_vs_concrete_never_errors(self):
        # a symbolic dim against concrete 4 is not provably wrong
        sd = SameDiff()
        a = sd.placeholder("a", (None, 8))
        b = sd.var("b", np.zeros((4, 8), np.float32))
        out = sd._record("add", [a, b])
        report = check_samediff(sd)
        assert report.findings == []
        assert report.avals[out.name].shape == (4, 8)

    def test_broadcast_shapes_symbolic(self):
        n = Dim("n")
        assert broadcast_shapes([(n, 128), (128,)]) == (n, 128)
        assert broadcast_shapes([(n, 1), (1, 5)]) == (n, 5)
        with pytest.raises(BroadcastError):
            broadcast_shapes([(2, 3), (4, 5)])

    def test_promotion_surprise_predicate(self):
        f32, i32 = np.dtype(np.float32), np.dtype(np.int32)
        assert promotion_surprise([f32, f32]) is None
        assert promotion_surprise([f32, i32]) is None  # ordinary promotion
        # x64 off (how programs run here): stays int32, uint32 wraps;
        # x64 on: widens to int64 — a surprise either way
        u32 = np.dtype(np.uint32)
        assert promotion_surprise([i32, u32])
        assert promotion_surprise([i32, np.dtype(np.uint16)]) is None  # fits
        assert promotion_surprise([np.dtype(np.int8), np.dtype(np.uint8)])
        import jax.numpy as jnp
        assert promotion_surprise([np.dtype(jnp.bfloat16), f32])  # mixed


# ---------------------------------------------------------------------------
# constant env + eval_shape fallback
# ---------------------------------------------------------------------------


class TestConstEnv:
    def test_shape_chain_stays_concrete(self):
        sd = fixtures.shape_chain()
        report = check_samediff(sd)
        assert report.findings == []
        assert report.avals["y"].shape == (4, 6)  # reshape_dynamic resolved

    def test_bad_dynamic_reshape_flagged(self):
        sd = SameDiff()
        x = sd.var("x", np.ones((6, 4), np.float32))
        tgt = sd.constant("tgt", np.asarray([5, 5], np.int64))
        sd.op("reshape_dynamic", x, tgt)
        report = check_samediff(sd)
        assert [f.rule for f in report.findings] == ["GC005"]

    def test_const_eval_matches_jax_promotion(self):
        # const-eval must run under JAX semantics: np.int32/np.int32
        # promotes to float64 on host but float32 under jax x32 — the
        # divergence made the optimizer's invariance checker raise a
        # phantom dtype change on valid graphs (review regression)
        sd = SameDiff()
        a = sd.constant("a", np.asarray([4, 6], np.int32))
        b = sd.constant("b", np.asarray([2, 3], np.int32))
        out = a / b
        out.rename("out")
        report = check_samediff(sd)
        assert report.avals["out"].dtype == np.dtype(np.float32)
        # end-to-end: fold + invariance checker agree (no PassInvariantError)
        np.testing.assert_allclose(sd.output({}, ["out"])["out"],
                                   [2.0, 2.0])

    def test_eval_shape_fallback_exact_on_concrete(self):
        sd = SameDiff()
        x = sd.placeholder("x", (5, 8))
        vals, idx = sd.op("top_k", x, k=3, n_out=2)
        report = check_samediff(sd)
        assert report.findings == []
        assert report.avals[vals.name].shape == (5, 3)
        assert report.avals[idx.name].dtype == np.dtype(np.int32)

    def test_control_flow_opaque_but_silent(self):
        import jax.numpy as jnp

        sd = SameDiff()
        xs = sd.placeholder("xs", (4, 3))
        y = sd.scan(lambda c, x: (c + x, c), jnp.zeros(3), xs)
        report = check_samediff(sd)
        assert report.findings == []  # local ops: unknown, no GC006
        assert report.avals[y.name].shape is None


# ---------------------------------------------------------------------------
# SameDiff surface: check() / validate=True
# ---------------------------------------------------------------------------


class TestFusedOpRules:
    """First-class rules for the optimizer's fusion-target registry ops
    (docs/OPTIMIZER.md § Fusion tier): symbolic-batch graphs must infer
    exact output shapes WITHOUT the jax.eval_shape probe (which cannot run
    over symbolic dims), and provable mismatches must flag GC codes."""

    def test_dot_product_attention_symbolic_batch(self):
        sd = SameDiff()
        q = sd.placeholder("q", (None, 4, 32, 16))
        k = sd.placeholder("k", (None, 4, 32, 16))
        v = sd.placeholder("v", (None, 4, 32, 16))
        m = sd.placeholder("m", (None, 1, 1, 32))
        sd.op("dot_product_attention", q, k, v, m, scaled=True).rename("o")
        report = check_samediff(sd)
        assert not report.findings
        aval = report.avals["o"]
        # a concrete trailing shape proves the RULE ran: the eval_shape
        # probe cannot produce one over a symbolic batch dim
        assert isinstance(aval.shape[0], Dim)
        assert aval.shape[1:] == (4, 32, 16)
        assert aval.dtype == np.dtype(np.float32)

    def test_dot_product_attention_causal_kwarg(self):
        sd = SameDiff()
        q = sd.placeholder("q", (None, 4, 32, 16))
        k = sd.placeholder("k", (None, 4, 32, 16))
        v = sd.placeholder("v", (None, 4, 32, 16))
        sd.op("dot_product_attention", q, k, v, causal=True).rename("o")
        report = check_samediff(sd)
        assert not report.findings
        assert report.avals["o"].shape[1:] == (4, 32, 16)

    def test_dot_product_attention_head_dim_mismatch(self):
        sd = SameDiff()
        q = sd.placeholder("q", (2, 4, 32, 16))
        k = sd.placeholder("k", (2, 4, 32, 24))  # dk mismatch
        v = sd.placeholder("v", (2, 4, 32, 16))
        sd.op("dot_product_attention", q, k, v)
        report = check_samediff(sd)
        assert any(f.rule == "GC002" and "head dims" in f.message
                   for f in report.findings)

    def test_dot_product_attention_zero_d_mask_flagged_not_crashed(self):
        sd = SameDiff()
        q = sd.placeholder("q", (2, 4, 32, 16))
        k = sd.placeholder("k", (2, 4, 32, 16))
        v = sd.placeholder("v", (2, 4, 32, 16))
        m = sd.placeholder("m", ())  # 0-d mask: finding, not IndexError
        sd.op("dot_product_attention", q, k, v, m)
        report = check_samediff(sd)
        assert any(f.rule == "GC001" and "0-d" in f.message
                   for f in report.findings)

    def test_dot_product_attention_kv_length_mismatch(self):
        sd = SameDiff()
        q = sd.placeholder("q", (2, 4, 32, 16))
        k = sd.placeholder("k", (2, 4, 32, 16))
        v = sd.placeholder("v", (2, 4, 48, 16))  # Lk mismatch
        sd.op("dot_product_attention", q, k, v)
        report = check_samediff(sd)
        assert any(f.rule == "GC002" and "sequence lengths" in f.message
                   for f in report.findings)

    @staticmethod
    def _paged_graph(q=(2, 4, 16), kv=(3, 2, 6, 8, 64), pt=(2, 3),
                     pt_dtype=np.int32, **kwargs):
        sd = SameDiff()
        qv = sd.placeholder("q", q)
        pool = sd.var("kv", np.zeros(kv, np.float32))
        ptv = sd.placeholder("pt", pt, dtype=pt_dtype)
        sl = sd.placeholder("sl", (q[0],), dtype=np.int32)
        sd.op("paged_decode_attention", qv, pool, ptv, sl,
              **kwargs).rename("o")
        return check_samediff(sd)

    def test_paged_decode_attention_symbolic_slots(self):
        report = self._paged_graph(q=(None, 4, 16), pt=(None, 3), layer=2)
        assert not report.findings
        aval = report.avals["o"]
        assert isinstance(aval.shape[0], Dim) and aval.shape[1:] == (4, 16)

    @pytest.mark.parametrize("graph,rule,needle", [
        (dict(pt=(2, 3, 1)), "GC001", "page_table"),            # rank 3
        (dict(kv=(6, 8, 4, 16)), "GC001", "kv_pages"),  # a layer's K alone
        (dict(pt_dtype=np.float32), "GC003", "not integral"),
        (dict(kv=(3, 2, 6, 8, 48)), "GC002", "row widths"),  # 4 x 16 != 48
        (dict(kv=(3, 1, 6, 8, 64)), "GC002", "K and V"),
        (dict(layer=3), "GC002", "layer 3"),
        (dict(q=(2, 4, 16), pt=(5, 3)), "GC002", "slot counts"),
    ])
    def test_paged_decode_attention_findings(self, graph, rule, needle):
        report = self._paged_graph(**graph)
        assert any(f.rule == rule and needle in f.message
                   for f in report.findings), report.findings

    def test_fused_matmul_bias_act_symbolic_batch(self):
        sd = SameDiff()
        x = sd.placeholder("x", (None, 32))
        w = sd.var("w", np.zeros((32, 8), np.float32))
        b = sd.var("b", np.zeros(8, np.float32))
        sd.op("fused_matmul_bias_act", x, w, b,
              activation="gelu_exact").rename("o")
        report = check_samediff(sd)
        assert not report.findings
        aval = report.avals["o"]
        assert isinstance(aval.shape[0], Dim) and aval.shape[1] == 8

    def test_fused_matmul_bias_act_findings(self):
        sd = SameDiff()
        x = sd.placeholder("x", (4, 32))
        w = sd.var("w", np.zeros((16, 8), np.float32))  # contraction
        sd.op("fused_matmul_bias_act", x, w)
        report = check_samediff(sd)
        assert any(f.rule == "GC002" for f in report.findings)

        sd = SameDiff()
        x = sd.placeholder("x", (4, 32))
        w = sd.var("w", np.zeros((32, 8), np.float32))
        b = sd.var("b", np.zeros((3,), np.float32))  # bias won't broadcast
        sd.op("fused_matmul_bias_act", x, w, b)
        report = check_samediff(sd)
        assert any(f.rule == "GC002" and "bias" in f.message
                   for f in report.findings)

        sd = SameDiff()
        x = sd.placeholder("x", (4, 32))
        w = sd.var("w", np.zeros((32, 8), np.float32))
        sd.op("fused_matmul_bias_act", x, w, activation="swish")
        report = check_samediff(sd)
        assert any(f.rule == "GC001" and "activation" in f.message
                   for f in report.findings)

    def test_zero_probe_fallbacks_on_fused_fixture(self):
        # the acceptance criterion: the fused-graph fixture verifies with
        # no GC006 opacity findings — i.e. every fused op resolved through
        # a first-class rule, never the eval_shape probe (which is
        # impossible here: the fixture's batch dims are symbolic)
        report = check_samediff(fixtures.fused_graph_sym_batch(),
                                graph_name="zoo/fused_graph_sym_batch")
        assert not report.findings
        for out in ("att", "causal_att", "h", "decoded"):
            assert report.avals[out].shape is not None


class TestTunedKernelRules:
    """First-class rules for the PR-9 kernel set (fused_layer_norm,
    fused_updater_step, quantize/dequantize_int8, matmul_int8): the
    symbolic-batch fixture must infer exact shapes with ZERO eval_shape
    probe fallbacks, and provable mismatches must flag GC codes."""

    def test_rules_registered(self):
        from deeplearning4j_tpu.analysis.rules import RULES

        for op in ("fused_layer_norm", "fused_updater_step",
                   "quantize_int8", "dequantize_int8", "matmul_int8"):
            assert op in RULES, op

    def test_zero_probe_fallbacks_on_tuned_fixture(self):
        report = check_samediff(fixtures.tuned_kernels_sym_batch(),
                                graph_name="zoo/tuned_kernels_sym_batch")
        assert not report.findings
        y = report.avals["y"]
        assert isinstance(y.shape[0], Dim)  # rule ran: probe cannot do this
        assert y.shape[1] == 128
        assert report.avals["new_p"].shape == (128,)

    def test_fused_layer_norm_gain_mismatch(self):
        sd = SameDiff()
        x = sd.placeholder("x", (None, 128))
        g = sd.var("g", np.ones(64, np.float32))
        sd.op("fused_layer_norm", x, g, activation="gelu")
        report = check_samediff(sd)
        assert any(f.rule == "GC002" and "gain" in f.message
                   for f in report.findings)

    def test_fused_layer_norm_bad_activation(self):
        sd = SameDiff()
        x = sd.placeholder("x", (4, 128))
        g = sd.var("g", np.ones(128, np.float32))
        sd.op("fused_layer_norm", x, g, activation="swish")
        report = check_samediff(sd)
        assert any(f.rule == "GC001" and "activation" in f.message
                   for f in report.findings)

    def test_fused_updater_step_state_shape_mismatch(self):
        sd = SameDiff()
        p = sd.var("p", np.zeros(8, np.float32))
        g = sd.var("g", np.zeros(8, np.float32))
        m = sd.var("m", np.zeros(4, np.float32))  # wrong leaf shape
        lr = sd.constant(np.float32(1e-3))
        step = sd.constant(np.float32(0.0))
        sd.op("fused_updater_step", p, g, lr, step, m, kind="Nesterovs",
              n_out=2)
        report = check_samediff(sd)
        assert any(f.rule == "GC002" and "state[0]" in f.message
                   for f in report.findings)

    def test_matmul_int8_non_int8_weights(self):
        sd = SameDiff()
        x = sd.placeholder("x", (None, 128))
        w = sd.var("w", np.zeros((128, 64), np.float32))
        ws = sd.var("ws", np.ones(64, np.float32))
        sd.op("matmul_int8", x, w, ws)
        report = check_samediff(sd)
        assert any(f.rule == "GC003" and "int8" in f.message
                   for f in report.findings)

    def test_quantize_int8_axis_out_of_range(self):
        sd = SameDiff()
        x = sd.placeholder("x", (4, 8))
        sd.op("quantize_int8", x, axis=5, n_out=2)
        report = check_samediff(sd)
        assert any(f.rule == "GC001" and "axis" in f.message
                   for f in report.findings)

    def test_quantize_int8_tuple_axis_checks_clean(self):
        # the impl accepts jnp.max-style axis tuples; the rule must not
        # crash on them and derives the keepdims scale shape
        sd = SameDiff()
        x = sd.placeholder("x", (4, 8))
        q, s = sd.op("quantize_int8", x, axis=(0, 1), n_out=2)
        q.rename("q")
        s.rename("s")
        report = check_samediff(sd)
        assert not report.findings
        assert report.avals["s"].shape == (1, 1)

    def test_fused_updater_step_kind_and_arity_flagged(self):
        # unknown kind and wrong state count both raise at trace time —
        # the rule must flag them pre-trace
        def graph(kind, n_state):
            sd = SameDiff()
            p = sd.var("p", np.zeros(8, np.float32))
            g = sd.var("g", np.zeros(8, np.float32))
            lr = sd.constant(np.float32(1e-3))
            step = sd.constant(np.float32(0.0))
            st = [sd.var(f"s{i}", np.zeros(8, np.float32))
                  for i in range(n_state)]
            sd.op("fused_updater_step", p, g, lr, step, *st, kind=kind,
                  n_out=1 + n_state)
            return sd

        report = check_samediff(graph("Adm", 0))
        assert any(f.rule == "GC001" and "unknown updater kind"
                   in f.message for f in report.findings)
        report = check_samediff(graph("Adam", 1))
        assert any(f.rule == "GC001" and "expected 2 state" in f.message
                   for f in report.findings)
        assert not check_samediff(graph("Adam", 2)).findings

    def test_fused_updater_step_rank_mismatch_flagged(self):
        # zip() truncation must not hide a rank mismatch
        sd = SameDiff()
        p = sd.var("p", np.zeros(4, np.float32))
        g = sd.var("g", np.zeros((4, 5), np.float32))
        lr = sd.constant(np.float32(1e-3))
        step = sd.constant(np.float32(0.0))
        sd.op("fused_updater_step", p, g, lr, step, kind="Sgd")
        report = check_samediff(sd)
        assert any(f.rule == "GC002" and "grad" in f.message
                   for f in report.findings)

    def test_fused_layer_norm_non_trailing_axis_flagged(self):
        sd = SameDiff()
        x = sd.placeholder("x", (4, 128))
        g = sd.var("g", np.ones(128, np.float32))
        sd.op("fused_layer_norm", x, g, axis=0)
        report = check_samediff(sd)
        assert any(f.rule == "GC001" and "trailing" in f.message
                   for f in report.findings)


class TestSameDiffWiring:
    def test_check_populates_last_report(self):
        sd = SameDiff()
        x = sd.placeholder("x", (2, 3))
        sd.math.tanh(x)
        assert sd.last_check_report is None
        report = sd.check(name="wiring")
        assert sd.last_check_report is report
        assert report.ok

    def test_validate_raises_before_trace(self):
        sd = SameDiff(validate=True)
        a = sd.placeholder("a", (2, 3))
        b = sd.placeholder("b", (4, 5))
        out = a + b
        with pytest.raises(GraphCheckError) as ei:
            sd.output({"a": np.ones((2, 3), np.float32),
                       "b": np.ones((4, 5), np.float32)}, [out.name])
        assert "GC002" in str(ei.value)

    def test_validate_checks_only_requested_subgraph(self):
        # the broken branch is NOT an ancestor of the requested output —
        # validate must not block execution (mirrors trace semantics)
        sd = SameDiff(validate=True)
        x = sd.placeholder("x", (2, 3))
        good = (x * 2.0).sum()
        good.rename("ok")
        x.reshape(999)  # dead and impossible
        res = sd.output({"x": np.ones((2, 3), np.float32)}, ["ok"])
        assert float(res["ok"]) == 12.0

    def test_validate_off_by_default(self):
        sd = SameDiff()
        a = sd.placeholder("a", (3,))
        (a + a).rename("y")
        res = sd.output({"a": np.ones(3, np.float32)}, ["y"])
        np.testing.assert_allclose(res["y"], 2 * np.ones(3))


# ---------------------------------------------------------------------------
# importer wiring
# ---------------------------------------------------------------------------


class TestImporterWiring:
    def _bad_ir(self):
        from deeplearning4j_tpu.imports.ir import IRGraph, IRNode

        init = {"w": np.zeros((7, 3), np.float32)}  # wrong contraction dim
        nodes = [IRNode("mm", "MatMul", ["x", "w"], ["y"])]
        return IRGraph(nodes=nodes, initializers=init,
                       inputs=[("x", (2, 8))], outputs=["y"], name="onnx")

    def test_onnx_importer_raises_with_provenance(self):
        from deeplearning4j_tpu.imports.onnx_import import OnnxImporter

        with pytest.raises(GraphCheckError) as ei:
            OnnxImporter().run_import(self._bad_ir())
        msg = str(ei.value)
        assert "GC002" in msg and "'y'" in msg  # source node name surfaces

    def test_validate_false_opts_out(self):
        from deeplearning4j_tpu.imports.onnx_import import OnnxImporter

        sd = OnnxImporter(validate=False).run_import(self._bad_ir())
        assert sd.last_check_report is None

    def test_clean_import_attaches_report(self):
        sd = fixtures.onnx_mini_import()
        assert sd.last_check_report is not None
        assert sd.last_check_report.ok


# ---------------------------------------------------------------------------
# check_network (the Keras-import surface)
# ---------------------------------------------------------------------------


class TestCheckNetwork:
    def test_clean_sequential(self):
        from deeplearning4j_tpu import nn

        conf = (nn.builder().seed(0)
                .layer(nn.DenseLayer(n_out=8, activation="relu"))
                .layer(nn.OutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(nn.InputType.feed_forward(4))
                .build())
        report = check_network(conf, graph_name="net/clean")
        assert report.findings == []

    def test_n_in_contradiction_flagged(self):
        from deeplearning4j_tpu import nn

        conf = (nn.builder().seed(0)
                .layer(nn.DenseLayer(n_in=4, n_out=8, activation="relu"))
                .layer(nn.DenseLayer(n_in=9, n_out=2))  # 8 flows in
                .set_input_type(nn.InputType.feed_forward(4))
                .build())
        report = check_network(conf, graph_name="net/bad")
        assert any(f.rule == "GC002" and "n_in=9" in f.message
                   for f in report.findings), [
            f.render() for f in report.findings]

    def test_keras_import_runs_check(self):
        # the sequential Keras path attaches last_check_report
        from deeplearning4j_tpu.imports.keras_import import (
            import_keras_sequential_config)

        config = {"class_name": "Sequential", "config": {"layers": [
            {"class_name": "Dense",
             "config": {"name": "dense", "units": 8, "activation": "relu",
                        "use_bias": True, "batch_input_shape": [None, 4]}},
            {"class_name": "Dense",
             "config": {"name": "dense_1", "units": 2,
                        "activation": "softmax", "use_bias": True}},
        ]}}
        r = np.random.RandomState(0)
        weights = {"dense": [r.randn(4, 8).astype(np.float32),
                             np.zeros(8, np.float32)],
                   "dense_1": [r.randn(8, 2).astype(np.float32),
                               np.zeros(2, np.float32)]}
        net = import_keras_sequential_config(config, weights)
        assert net.last_check_report is not None
        assert net.last_check_report.ok


# ---------------------------------------------------------------------------
# CLI + baseline contract
# ---------------------------------------------------------------------------


class TestCliAndBaseline:
    def test_cli_json_contract(self):
        proc = subprocess.run(
            [sys.executable, "tools/graftcheck.py", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        lines = [l for l in proc.stdout.splitlines() if l.strip()]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["tool"] == "graftcheck" and rec["new"] == 0

    def test_committed_baseline_is_empty(self):
        # the fixture zoo carries NO grandfathered debt; a finding there
        # is a regression, never baseline material
        with open(os.path.join(REPO, "check_baseline.json")) as fh:
            data = json.load(fh)
        assert data["findings"] == {}

    def test_write_baseline_refuses_growth(self, tmp_path):
        from deeplearning4j_tpu.lint.core import (
            Finding, load_baseline, write_baseline)

        path = str(tmp_path / "check_baseline.json")
        write_baseline(path, [], comment="test")
        bad = Finding(path="zoo/mlp_sym_batch", line=1, rule="GC002",
                      severity="error", message="seeded")
        refused = write_baseline(path, [bad], comment="test")
        assert refused == {bad.key: 1}
        assert load_baseline(path) == {}

    def test_all_codes_documented(self):
        """Every GC code has an entry in docs/ANALYSIS.md (the lint-suite
        doc ratchet, applied to graftcheck)."""
        doc = open(os.path.join(REPO, "docs", "ANALYSIS.md")).read()
        for code in GC_CODES:
            assert code in doc, f"{code} missing from docs/ANALYSIS.md"
