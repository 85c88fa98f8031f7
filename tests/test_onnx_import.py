"""ONNX import golden tests — the samediff-import-onnx golden pattern
(SURVEY §3.2): assemble an ONNX ModelProto, import to SameDiff, and compare
outputs elementwise against an independent oracle (numpy / torch).

No ONNX producer exists in this environment (no onnx package; torch's
exporter requires it), so models are assembled at the protobuf byte level
with the same wire codec the importer uses for decoding — the round trip
plus the independent-oracle forward checks both codec directions AND the
mapping rules.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.imports import protowire as pw
from deeplearning4j_tpu.imports.onnx_import import (
    OnnxImporter, import_onnx, parse_model,
)


# ---------------------------------------------------------------------------
# ModelProto assembly helpers — canonical home is
# deeplearning4j_tpu/testing/onnx_builder.py; re-exported here for
# the golden-test files that import them from this module.
# ---------------------------------------------------------------------------

from deeplearning4j_tpu.testing.onnx_builder import (  # noqa: F401,E402
    attr_proto, build_model, node_proto, tensor_proto, value_info)


def _run(sd, feeds, out):
    return sd.output(feeds, out)[out]


class TestOnnxParser:
    def test_tensor_round_trip(self):
        arr = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        model = build_model([], [("x", (1,))], [("x", (1,))], {"w": arr})
        ir = parse_model(model)
        np.testing.assert_array_equal(ir.initializers["w"], arr)

    def test_int64_tensor(self):
        arr = np.asarray([2, -1, 12], np.int64)
        model = build_model([], [("x", (1,))], [("x", (1,))], {"s": arr})
        ir = parse_model(model)
        np.testing.assert_array_equal(ir.initializers["s"], arr)

    def test_node_attrs(self):
        n = node_proto("Softmax", ["x"], ["y"], axis=-1)
        model = build_model([n], [("x", (2, 3))], [("y", (2, 3))], {})
        ir = parse_model(model)
        assert ir.nodes[0].op_type == "Softmax"
        assert ir.nodes[0].attrs["axis"] == -1
        assert ir.inputs == [("x", (2, 3))]
        assert ir.outputs == ["y"]


class TestOnnxImport:
    def test_mlp_golden(self):
        r = np.random.RandomState(0)
        w0 = r.randn(8, 4).astype(np.float32)  # Gemm transB: (out, in)
        b0 = r.randn(8).astype(np.float32)
        w1 = r.randn(3, 8).astype(np.float32)
        b1 = r.randn(3).astype(np.float32)
        nodes = [
            node_proto("Gemm", ["x", "w0", "b0"], ["h0"], transB=1),
            node_proto("Relu", ["h0"], ["h1"]),
            node_proto("Gemm", ["h1", "w1", "b1"], ["h2"], transB=1),
            node_proto("Softmax", ["h2"], ["y"], axis=-1),
        ]
        model = build_model(nodes, [("x", (5, 4))], [("y", (5, 3))],
                            {"w0": w0, "b0": b0, "w1": w1, "b1": b1})
        x = r.randn(5, 4).astype(np.float32)
        # independent numpy oracle
        h = np.maximum(x @ w0.T + b0, 0) @ w1.T + b1
        e = np.exp(h - h.max(axis=-1, keepdims=True))
        want = e / e.sum(axis=-1, keepdims=True)

        sd = import_onnx(model)
        got = _run(sd, {"x": x}, "y")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_cnn_golden_vs_torch(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        r = np.random.RandomState(1)
        x = r.randn(2, 3, 8, 8).astype(np.float32)
        w = (r.randn(4, 3, 3, 3) * 0.5).astype(np.float32)
        b = r.randn(4).astype(np.float32)
        gamma = (np.abs(r.randn(4)) + 0.5).astype(np.float32)
        beta = r.randn(4).astype(np.float32)
        mean = r.randn(4).astype(np.float32)
        var = (np.abs(r.randn(4)) + 0.5).astype(np.float32)
        wf = r.randn(5, 4).astype(np.float32)
        bf = r.randn(5).astype(np.float32)

        nodes = [
            node_proto("Conv", ["x", "w", "b"], ["c1"],
                       kernel_shape=[3, 3], strides=[1, 1],
                       pads=[1, 1, 1, 1]),
            node_proto("BatchNormalization",
                       ["c1", "gamma", "beta", "mean", "var"], ["bn"],
                       epsilon=1e-5),
            node_proto("Relu", ["bn"], ["r1"]),
            node_proto("MaxPool", ["r1"], ["p1"], kernel_shape=[2, 2],
                       strides=[2, 2]),
            node_proto("GlobalAveragePool", ["p1"], ["g1"]),
            node_proto("Flatten", ["g1"], ["f1"], axis=1),
            node_proto("Gemm", ["f1", "wf", "bf"], ["y"], transB=1),
        ]
        model = build_model(
            nodes, [("x", (2, 3, 8, 8))], [("y", (2, 5))],
            {"w": w, "b": b, "gamma": gamma, "beta": beta, "mean": mean,
             "var": var, "wf": wf, "bf": bf})

        with torch.no_grad():
            t = torch.from_numpy(x)
            t = F.conv2d(t, torch.from_numpy(w), torch.from_numpy(b),
                         padding=1)
            t = F.batch_norm(t, torch.from_numpy(mean), torch.from_numpy(var),
                             torch.from_numpy(gamma), torch.from_numpy(beta),
                             training=False, eps=1e-5)
            t = F.relu(t)
            t = F.max_pool2d(t, 2, 2)
            t = F.adaptive_avg_pool2d(t, 1).flatten(1)
            want = (t @ torch.from_numpy(wf).T + torch.from_numpy(bf)).numpy()

        sd = import_onnx(model)
        got = _run(sd, {"x": x}, "y")
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_depthwise_conv_golden_vs_torch(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        r = np.random.RandomState(2)
        x = r.randn(1, 4, 6, 6).astype(np.float32)
        w = r.randn(4, 1, 3, 3).astype(np.float32)
        nodes = [node_proto("Conv", ["x", "w"], ["y"], kernel_shape=[3, 3],
                            strides=[1, 1], pads=[0, 0, 0, 0], group=4)]
        model = build_model(nodes, [("x", (1, 4, 6, 6))], [("y", (1, 4, 4, 4))],
                            {"w": w})
        with torch.no_grad():
            want = F.conv2d(torch.from_numpy(x), torch.from_numpy(w),
                            groups=4).numpy()
        got = _run(import_onnx(model), {"x": x}, "y")
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_elementwise_reduce_chain(self):
        r = np.random.RandomState(3)
        x = r.randn(3, 6).astype(np.float32)
        c = r.randn(6).astype(np.float32)
        nodes = [
            node_proto("Add", ["x", "c"], ["a"]),
            node_proto("Clip", ["a"], ["cl"], min=-1.0, max=1.0),
            node_proto("Mul", ["cl", "cl"], ["m"]),
            node_proto("ReduceMean", ["m"], ["rm"], axes=[1], keepdims=0),
            node_proto("Sqrt", ["rm"], ["y"]),
        ]
        model = build_model(nodes, [("x", (3, 6))], [("y", (3,))], {"c": c})
        want = np.sqrt(np.mean(np.clip(x + c, -1, 1) ** 2, axis=1))
        got = _run(import_onnx(model), {"x": x}, "y")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_shape_ops_chain(self):
        r = np.random.RandomState(4)
        x = r.randn(2, 3, 4).astype(np.float32)
        shape = np.asarray([2, 12], np.int64)
        nodes = [
            node_proto("Transpose", ["x"], ["t"], perm=[0, 2, 1]),
            node_proto("Reshape", ["t", "shape"], ["rs"]),
            node_proto("Concat", ["rs", "rs"], ["cc"], axis=0),
            node_proto("Pad", ["cc"], ["y"], pads=[0, 1, 0, 1], value=0.5),
        ]
        model = build_model(nodes, [("x", (2, 3, 4))], [("y", (4, 14))],
                            {"shape": shape})
        t = x.transpose(0, 2, 1).reshape(2, 12)
        cc = np.concatenate([t, t], axis=0)
        want = np.pad(cc, [(0, 0), (1, 1)], constant_values=0.5)
        got = _run(import_onnx(model), {"x": x}, "y")
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_unsupported_op_message(self):
        nodes = [node_proto("NonexistentOp", ["x"], ["y"])]
        model = build_model(nodes, [("x", (1,))], [("y", (1,))], {})
        with pytest.raises(NotImplementedError, match="NonexistentOp"):
            import_onnx(model)

    def test_supported_ops_listing(self):
        ops = OnnxImporter().supported_ops()
        assert len(ops) >= 45
        assert "Conv" in ops and "Gemm" in ops and "BatchNormalization" in ops

    def test_avgpool_pads_excludes_padding(self):
        torch = pytest.importorskip("torch")
        import torch.nn.functional as F

        r = np.random.RandomState(5)
        x = r.randn(1, 2, 6, 6).astype(np.float32)
        nodes = [node_proto("AveragePool", ["x"], ["y"], kernel_shape=[3, 3],
                            strides=[1, 1], pads=[1, 1, 1, 1])]
        model = build_model(nodes, [("x", (1, 2, 6, 6))], [("y", (1, 2, 6, 6))], {})
        with torch.no_grad():
            want = F.avg_pool2d(torch.from_numpy(x), 3, 1, padding=1,
                                count_include_pad=False).numpy()
        got = _run(import_onnx(model), {"x": x}, "y")
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_unsqueeze_multiple_axes(self):
        x = np.random.RandomState(6).randn(3, 4).astype(np.float32)
        ax = np.asarray([0, 3], np.int64)
        nodes = [node_proto("Unsqueeze", ["x", "ax"], ["y"])]
        model = build_model(nodes, [("x", (3, 4))], [("y", (1, 3, 4, 1))],
                            {"ax": ax})
        got = _run(import_onnx(model), {"x": x}, "y")
        assert got.shape == (1, 3, 4, 1)
        np.testing.assert_array_equal(got[0, :, :, 0], x)

    def test_grouped_conv_rejected(self):
        w = np.random.RandomState(7).randn(4, 2, 3, 3).astype(np.float32)
        nodes = [node_proto("Conv", ["x", "w"], ["y"], kernel_shape=[3, 3],
                            group=2)]
        model = build_model(nodes, [("x", (1, 4, 6, 6))], [("y", (1, 4, 4, 4))],
                            {"w": w})
        with pytest.raises(NotImplementedError, match="group"):
            import_onnx(model)


class TestOnnxRecurrentAndResize:
    """Round-4 widening: LSTM/GRU sequence ops + Resize, numpy oracles
    implementing the ONNX operator spec."""

    @staticmethod
    def _sig(v):
        return 1.0 / (1.0 + np.exp(-v))

    def test_lstm_forward(self):
        r = np.random.RandomState(0)
        t, n, i, h = 4, 2, 3, 5
        x = r.randn(t, n, i).astype(np.float32)
        W = r.randn(1, 4 * h, i).astype(np.float32)   # gates i,o,f,c
        R = r.randn(1, 4 * h, h).astype(np.float32)
        B = r.randn(1, 8 * h).astype(np.float32)
        nodes = [node_proto("LSTM", ["x", "W", "R", "B"],
                            ["Y", "Y_h", "Y_c"], hidden_size=h)]
        model = build_model(nodes, [("x", (t, n, i))],
                            [("Y", (t, 1, n, h)), ("Y_h", (1, n, h)),
                             ("Y_c", (1, n, h))],
                            {"W": W, "R": R, "B": B})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        res = sd.output({"x": x}, ["Y", "Y_h", "Y_c"])

        # ONNX LSTM oracle (spec equations, gates i,o,f,c)
        Wi, Wo, Wf, Wc = np.split(W[0], 4)
        Ri, Ro, Rf, Rc = np.split(R[0], 4)
        Wb, Rb = np.split(B[0], 2)
        bi, bo, bf, bc = np.split(Wb, 4)
        rbi, rbo, rbf, rbc = np.split(Rb, 4)
        hh = np.zeros((n, h), np.float32)
        cc = np.zeros((n, h), np.float32)
        Y = np.zeros((t, 1, n, h), np.float32)
        for s in range(t):
            xi = x[s]
            it = self._sig(xi @ Wi.T + hh @ Ri.T + bi + rbi)
            ot = self._sig(xi @ Wo.T + hh @ Ro.T + bo + rbo)
            ft = self._sig(xi @ Wf.T + hh @ Rf.T + bf + rbf)
            ct = np.tanh(xi @ Wc.T + hh @ Rc.T + bc + rbc)
            cc = ft * cc + it * ct
            hh = ot * np.tanh(cc)
            Y[s, 0] = hh
        np.testing.assert_allclose(res["Y"], Y, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(res["Y_h"][0], hh, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(res["Y_c"][0], cc, rtol=1e-4, atol=1e-5)

    def test_gru_forward_both_lbr(self):
        r = np.random.RandomState(1)
        t, n, i, h = 3, 2, 4, 3
        x = r.randn(t, n, i).astype(np.float32)
        W = r.randn(1, 3 * h, i).astype(np.float32)   # gates z,r,h
        R = r.randn(1, 3 * h, h).astype(np.float32)
        B = r.randn(1, 6 * h).astype(np.float32)
        from deeplearning4j_tpu.imports import import_onnx

        for lbr in (0, 1):
            nodes = [node_proto("GRU", ["x", "W", "R", "B"], ["Y", "Y_h"],
                                hidden_size=h, linear_before_reset=lbr)]
            model = build_model(nodes, [("x", (t, n, i))],
                                [("Y", (t, 1, n, h)), ("Y_h", (1, n, h))],
                                {"W": W, "R": R, "B": B})
            sd = import_onnx(bytes(model))
            res = sd.output({"x": x}, ["Y", "Y_h"])

            Wz, Wr, Wh = np.split(W[0], 3)
            Rz, Rr, Rh = np.split(R[0], 3)
            Wb, Rb = np.split(B[0], 2)
            bz, br, bh = np.split(Wb, 3)
            rbz, rbr, rbh = np.split(Rb, 3)
            hh = np.zeros((n, h), np.float32)
            Y = np.zeros((t, 1, n, h), np.float32)
            for s in range(t):
                xi = x[s]
                zt = self._sig(xi @ Wz.T + hh @ Rz.T + bz + rbz)
                rt = self._sig(xi @ Wr.T + hh @ Rr.T + br + rbr)
                if lbr:
                    ht = np.tanh(xi @ Wh.T + rt * (hh @ Rh.T + rbh) + bh)
                else:
                    ht = np.tanh(xi @ Wh.T + (rt * hh) @ Rh.T + bh + rbh)
                hh = (1.0 - zt) * ht + zt * hh
                Y[s, 0] = hh
            np.testing.assert_allclose(res["Y"], Y, rtol=1e-4, atol=1e-5,
                                       err_msg=f"lbr={lbr}")
            np.testing.assert_allclose(res["Y_h"][0], hh, rtol=1e-4,
                                       atol=1e-5)

    def test_resize_bilinear_half_pixel(self):
        r = np.random.RandomState(2)
        x = r.rand(1, 2, 4, 4).astype(np.float32)  # NCHW
        sizes = np.asarray([1, 2, 8, 8], np.int64)
        nodes = [node_proto("Resize", ["x", "", "", "sizes"], ["y"],
                            mode="linear",
                            coordinate_transformation_mode="half_pixel")]
        model = build_model(nodes, [("x", (1, 2, 4, 4))],
                            [("y", (1, 2, 8, 8))], {"sizes": sizes})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        got = sd.output({"x": x}, "y")["y"]
        assert got.shape == (1, 2, 8, 8)

        # half-pixel bilinear oracle
        def bilinear(img, oh, ow):
            ih, iw = img.shape
            out = np.zeros((oh, ow), np.float32)
            for a in range(oh):
                for b in range(ow):
                    sy = (a + 0.5) * ih / oh - 0.5
                    sx = (b + 0.5) * iw / ow - 0.5
                    y0 = int(np.floor(sy)); x0 = int(np.floor(sx))
                    dy = sy - y0; dx = sx - x0
                    y0c = np.clip([y0, y0 + 1], 0, ih - 1)
                    x0c = np.clip([x0, x0 + 1], 0, iw - 1)
                    out[a, b] = (
                        img[y0c[0], x0c[0]] * (1 - dy) * (1 - dx)
                        + img[y0c[0], x0c[1]] * (1 - dy) * dx
                        + img[y0c[1], x0c[0]] * dy * (1 - dx)
                        + img[y0c[1], x0c[1]] * dy * dx)
            return out

        want = np.stack([bilinear(x[0, c], 8, 8) for c in range(2)])[None]
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    def test_resize_rejects_other_transform(self):
        import pytest

        sizes = np.asarray([1, 1, 2, 2], np.int64)
        nodes = [node_proto("Resize", ["x", "", "", "sizes"], ["y"],
                            mode="linear",
                            coordinate_transformation_mode="align_corners")]
        model = build_model(nodes, [("x", (1, 1, 4, 4))],
                            [("y", (1, 1, 2, 2))], {"sizes": sizes})
        from deeplearning4j_tpu.imports import import_onnx

        with pytest.raises(NotImplementedError, match="align_corners"):
            import_onnx(bytes(model))

    def test_lstm_weights_are_trainable(self):
        """The gate re-packing is recorded in-graph, so gradients flow to
        the ORIGINAL imported W/R/B variables (fine-tune contract)."""
        r = np.random.RandomState(3)
        t, n, i, h = 3, 2, 3, 4
        x = r.randn(t, n, i).astype(np.float32)
        W = r.randn(1, 4 * h, i).astype(np.float32)
        R = r.randn(1, 4 * h, h).astype(np.float32)
        B = r.randn(1, 8 * h).astype(np.float32)
        nodes = [node_proto("LSTM", ["x", "W", "R", "B"],
                            ["Y", "Y_h", "Y_c"], hidden_size=h)]
        model = build_model(nodes, [("x", (t, n, i))],
                            [("Y", (t, 1, n, h))], {"W": W, "R": R, "B": B})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        assert sd._vars["W"].vtype == "VARIABLE"
        loss = sd._record("reduce_mean", [sd._vars["Y"]],
                          {"axes": None, "keepdims": False}).rename("l2loss")
        grads = sd.calculate_gradients({"x": x}, "l2loss", wrt=["W", "R", "B"])
        for k in ("W", "R", "B"):
            assert np.isfinite(grads[k]).all()
            assert np.abs(grads[k]).max() > 0, f"zero grad for {k}"

    def test_lstm_rejects_initial_state_and_seqlens(self):
        import pytest

        r = np.random.RandomState(4)
        h = 3
        W = r.randn(1, 4 * h, 2).astype(np.float32)
        R = r.randn(1, 4 * h, h).astype(np.float32)
        h0 = np.zeros((1, 2, h), np.float32)
        from deeplearning4j_tpu.imports import import_onnx

        # initial_h on slot 5 with EMPTY B/seq_lens slots — the guard must
        # check wire slots, not the compacted ins list
        nodes = [node_proto("LSTM", ["x", "W", "R", "", "", "h0"], ["Y"],
                            hidden_size=h)]
        model = build_model(nodes, [("x", (2, 2, 2))], [("Y", (2, 1, 2, h))],
                            {"W": W, "R": R, "h0": h0})
        with pytest.raises(NotImplementedError, match="initial_h"):
            import_onnx(bytes(model))

    def test_resize_from_scales(self):
        r = np.random.RandomState(5)
        x = r.rand(1, 2, 4, 4).astype(np.float32)
        scales = np.asarray([1.0, 1.0, 2.0, 2.0], np.float32)
        nodes = [node_proto("Resize", ["x", "", "scales"], ["y"],
                            mode="nearest",
                            coordinate_transformation_mode="half_pixel")]
        model = build_model(nodes, [("x", (1, 2, 4, 4))],
                            [("y", (1, 2, 8, 8))], {"scales": scales})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        got = sd.output({"x": x}, "y")["y"]
        assert got.shape == (1, 2, 8, 8)
        np.testing.assert_allclose(got[0, 0, ::2, ::2], x[0, 0], atol=1e-6)


class TestOnnxRound4Breadth:
    def test_einsum_gathernd_cumsum(self):
        r = np.random.RandomState(0)
        a = r.randn(2, 3, 4).astype(np.float32)
        b = r.randn(2, 4, 5).astype(np.float32)
        idx = np.asarray([[0, 1], [1, 2]], np.int64)
        nodes = [
            node_proto("Einsum", ["a", "b"], ["e"], equation="bij,bjk->bik"),
            node_proto("GatherND", ["a", "idx"], ["g"]),
            node_proto("CumSum", ["a", "ax"], ["c"]),
        ]
        model = build_model(nodes, [("a", (2, 3, 4)), ("b", (2, 4, 5))],
                            [("e", (2, 3, 5)), ("g", (2, 4)),
                             ("c", (2, 3, 4))],
                            {"idx": idx, "ax": np.asarray(1, np.int64)})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        res = sd.output({"a": a, "b": b}, ["e", "g", "c"])
        np.testing.assert_allclose(res["e"], np.einsum("bij,bjk->bik", a, b),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(res["g"], a[[0, 1], [1, 2]], rtol=1e-6)
        np.testing.assert_allclose(res["c"], np.cumsum(a, axis=1),
                                   rtol=1e-5, atol=1e-5)

    def test_trilu_not_isnan_hardmax(self):
        r = np.random.RandomState(1)
        x = r.randn(4, 4).astype(np.float32)
        nodes = [
            node_proto("Trilu", ["x"], ["u"], upper=1),
            node_proto("Hardmax", ["x"], ["h"]),
            node_proto("IsNaN", ["x"], ["n"]),
            node_proto("Not", ["n"], ["nn"]),
        ]
        model = build_model(nodes, [("x", (4, 4))],
                            [("u", (4, 4)), ("h", (4, 4)), ("nn", (4, 4))],
                            {})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        res = sd.output({"x": x}, ["u", "h", "nn"])
        np.testing.assert_allclose(res["u"], np.triu(x), rtol=1e-6)
        want_h = (x == x.max(axis=-1, keepdims=True)).astype(np.float32)
        np.testing.assert_allclose(res["h"], want_h)
        assert res["nn"].all()  # nothing is NaN

    def test_lp_norm_and_mvn(self):
        r = np.random.RandomState(2)
        x = r.randn(3, 6).astype(np.float32)
        xc = r.randn(2, 3, 4, 4).astype(np.float32)
        nodes = [node_proto("LpNormalization", ["x"], ["l"], p=2, axis=-1),
                 node_proto("MeanVarianceNormalization", ["xc"], ["m"])]
        model = build_model(nodes, [("x", (3, 6)), ("xc", (2, 3, 4, 4))],
                            [("l", (3, 6)), ("m", (2, 3, 4, 4))], {})
        from deeplearning4j_tpu.imports import import_onnx

        sd = import_onnx(bytes(model))
        res = sd.output({"x": x, "xc": xc}, ["l", "m"])
        np.testing.assert_allclose(
            res["l"], x / np.linalg.norm(x, axis=-1, keepdims=True),
            rtol=1e-4, atol=1e-5)
        mean = xc.mean(axis=(0, 2, 3), keepdims=True)
        var = ((xc - mean) ** 2).mean(axis=(0, 2, 3), keepdims=True)
        np.testing.assert_allclose(res["m"], (xc - mean) / np.sqrt(var + 1e-9),
                                   rtol=1e-3, atol=1e-4)
