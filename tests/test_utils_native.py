"""Profiling, stats-UI shim, and native codec tests (SURVEY §6.1, §6.5,
§5.3 — OpProfiler/ProfilingListener/StatsListener + native-lib patterns)."""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu import nn
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.utils.profiling import (
    OpProfiler, ChromeTraceWriter, ProfilingListener, ProfileAnalyzer,
)
from deeplearning4j_tpu.utils.stats import (
    StatsStorage, FileStatsStorage, StatsListener,
)
from deeplearning4j_tpu import native_ops


def xor():
    rng = np.random.RandomState(0)
    x = rng.rand(128, 2).astype(np.float32)
    y_id = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.5)).astype(int)
    return x, np.eye(2, dtype=np.float32)[y_id]


def small_net():
    return nn.MultiLayerNetwork(
        nn.builder().seed(1).updater(nn.Adam(learning_rate=0.02)).list()
        .layer(nn.DenseLayer(n_out=8, activation="tanh"))
        .layer(nn.OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .set_input_type(nn.InputType.feed_forward(2)).build()
    ).init()


class TestProfiling:
    def test_op_profiler_counts(self):
        p = OpProfiler.instance()
        p.reset()
        p.start()
        p.record("conv2d", 0.001)
        p.record("conv2d", 0.002)
        p.record("matmul")
        p.stop()
        assert p.counts["conv2d"] == 2
        assert "conv2d" in p.stats()

    def test_chrome_trace_writer(self, tmp_path):
        w = ChromeTraceWriter()
        with w.span("step1", iteration=1):
            pass
        w.instant("epoch_end")
        path = str(tmp_path / "trace.json")
        w.write(path)
        data = json.load(open(path))
        assert len(data["traceEvents"]) == 2
        assert data["traceEvents"][0]["ph"] == "X"

    def test_profiling_listener_writes_trace(self, tmp_path):
        x, y = xor()
        net = small_net()
        path = str(tmp_path / "train_trace.json")
        net.set_listeners(ProfilingListener(path))
        net.fit(x, y, epochs=1, batch_size=32)
        data = json.load(open(path))
        steps = [e for e in data["traceEvents"] if e.get("cat") == "train_step"]
        assert len(steps) == 3  # 4 batches → 3 complete inter-iteration spans

    def test_profile_analyzer_compare(self, tmp_path):
        a, b = ChromeTraceWriter(), ChromeTraceWriter()
        with a.span("x", category="step"):
            pass
        with b.span("x", category="step"):
            pass
        pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        a.write(pa)
        b.write(pb)
        cmp = ProfileAnalyzer.compare(pa, pb)
        assert "step" in cmp and "ratio" in cmp["step"]


class TestStatsListener:
    def test_collects_scores_and_ratios(self):
        x, y = xor()
        net = small_net()
        storage = StatsStorage()
        net.set_listeners(StatsListener(storage))
        net.fit(x, y, epochs=2, batch_size=64)
        assert len(storage.session_scores()) == 4
        latest = storage.latest()
        key = "0_W"
        assert key in latest["layers"]
        assert "update_ratio" in latest["layers"][key]  # the dead-LR chart
        assert latest["layers"][key]["update_ratio"] > 0

    def test_file_storage_round_trip(self, tmp_path):
        path = str(tmp_path / "stats.jsonl")
        s = FileStatsStorage(path)
        s.put({"score": 1.0, "iteration": 1})
        s2 = FileStatsStorage(path)
        assert s2.session_scores() == [1.0]

    def test_histograms(self):
        x, y = xor()
        net = small_net()
        storage = StatsStorage()
        net.set_listeners(StatsListener(storage, collect_histograms=True))
        net.fit(x, y, epochs=1, batch_size=128)
        assert "histogram" in storage.latest()["layers"]["0_W"]


class TestNativeCodec:
    def test_native_lib_builds(self):
        assert native_ops.native_available(), "cmake build of native codec failed"

    def test_encode_decode_round_trip(self):
        g = np.array([0.5, -0.2, 1.5, -2.0, 0.0, 0.9], np.float32)
        idx, residual = native_ops.threshold_encode(g, 1.0)
        assert list(idx) == [3, -4]
        decoded = native_ops.threshold_decode(idx, 1.0, g.size)
        np.testing.assert_allclose(decoded + residual, g, rtol=1e-6)

    def test_capacity_bound(self):
        g = np.full(100, 2.0, np.float32)
        idx, residual = native_ops.threshold_encode(g, 1.0, capacity=10)
        assert idx.size == 10
        assert residual[0] == pytest.approx(1.0)
        assert residual[50] == pytest.approx(2.0)  # untouched past capacity

    def test_bitmap_round_trip(self):
        g = np.array([0.5, -1.5, 2.5, 0.0], np.float32)
        bits, residual, nz = native_ops.bitmap_encode(g, 1.0)
        assert nz == 2
        decoded = native_ops.bitmap_decode(bits, 1.0, g.size)
        np.testing.assert_allclose(decoded + residual, g, rtol=1e-6)

    def test_compression_ratio_semantics(self):
        """Sparse gradient → few indices: the Strom-2015 bandwidth win."""
        rng = np.random.RandomState(0)
        g = np.zeros(10000, np.float32)
        hot = rng.choice(10000, 50, replace=False)
        g[hot] = rng.randn(50) * 10
        idx, _ = native_ops.threshold_encode(g, 1.0)
        assert idx.size <= 50
        assert idx.size >= 40

    def test_matches_python_fallback(self):
        from deeplearning4j_tpu.native_ops.threshold import _py_encode

        rng = np.random.RandomState(1)
        g = rng.randn(512).astype(np.float32)
        idx_n, res_n = native_ops.threshold_encode(g, 0.8)
        idx_p, res_p = _py_encode(g.copy(), 0.8, 512)
        np.testing.assert_array_equal(idx_n, idx_p)
        np.testing.assert_allclose(res_n, res_p, rtol=1e-6)


class TestNativeRecordLoader:
    """Native CSV/IDX loader (native/record_loader.cpp) — native-vs-python
    equality, the libnd4j-style two-impl check."""

    def test_csv_native_matches_python(self):
        from deeplearning4j_tpu.native_ops import record_loader as rl

        text = "h1,h2,h3\n1.5,2,3\n4,,bad\n7,8.25,9\n"
        out = rl.csv_to_float_matrix(text, 3, skip_rows=1)
        assert out.shape == (3, 3)
        np.testing.assert_allclose(out[0], [1.5, 2, 3])
        assert np.isnan(out[1, 1]) and np.isnan(out[1, 2])
        np.testing.assert_allclose(out[2], [7, 8.25, 9])
        if rl.native_loader_available():
            # force the python fallback and compare elementwise
            import deeplearning4j_tpu.native_ops.record_loader as mod

            orig = mod._loader_lib
            try:
                mod._loader_lib = lambda: None
                py = rl.csv_to_float_matrix(text, 3, skip_rows=1)
            finally:
                mod._loader_lib = orig
            np.testing.assert_array_equal(np.isnan(out), np.isnan(py))
            np.testing.assert_allclose(out[~np.isnan(out)], py[~np.isnan(py)])

    def test_csv_ragged_raises(self):
        from deeplearning4j_tpu.native_ops import record_loader as rl

        with pytest.raises(ValueError):
            rl.csv_to_float_matrix("1,2\n3\n", 2)

    def test_idx_round_trip(self):
        import struct

        from deeplearning4j_tpu.native_ops import record_loader as rl

        rng = np.random.RandomState(0)
        arr = rng.randint(0, 256, (4, 5, 6)).astype(np.uint8)
        buf = struct.pack(">BBBB", 0, 0, 0x08, 3)
        buf += struct.pack(">III", 4, 5, 6)
        buf += arr.tobytes()
        out = rl.idx_to_array(buf)
        assert out.shape == (4, 5, 6)
        np.testing.assert_allclose(out, arr.astype(np.float32) / 255.0)
        out2 = rl.idx_to_array(buf, scale=False)
        np.testing.assert_allclose(out2, arr.astype(np.float32))


class TestPixOps:
    """native/pixops.cpp kernels: normalize/standardize + murmur3
    (HashUtil role) — native and numpy fallback must agree bit-for-bit."""

    def test_u8_normalize_matches_numpy(self):
        from deeplearning4j_tpu.native_ops.pixops import u8_normalize
        r = np.random.RandomState(0)
        img = r.randint(0, 256, (4, 6, 3), np.uint8)
        out = u8_normalize(img, 1 / 255.0, 0.0)
        np.testing.assert_allclose(out, img.astype(np.float32) / 255.0,
                                   rtol=0, atol=1e-7)
        assert out.dtype == np.float32

    def test_u8_standardize_matches_numpy(self):
        from deeplearning4j_tpu.native_ops.pixops import u8_standardize
        r = np.random.RandomState(1)
        img = r.randint(0, 256, (2, 5, 5, 3), np.uint8)
        mean = np.asarray([100.0, 120.0, 140.0], np.float32)
        std = np.asarray([50.0, 60.0, 70.0], np.float32)
        out = u8_standardize(img, mean, std)
        np.testing.assert_allclose(
            out, (img.astype(np.float32) - mean) / std, rtol=1e-6, atol=1e-5)

    def test_murmur3_known_vectors(self):
        from deeplearning4j_tpu.native_ops.pixops import murmur3_32, _murmur3_py
        vectors = [(b"", 0, 0x0), (b"", 1, 0x514E28B7),
                   (b"abc", 0, 0xB3DD93FA), (b"hello", 0, 0x248BFA47)]
        for data, seed, want in vectors:
            assert murmur3_32(data, seed) == want
            assert _murmur3_py(data, seed) == want  # fallback bit-exact

    def test_murmur3_string_utf8(self):
        from deeplearning4j_tpu.native_ops.pixops import murmur3_32
        assert murmur3_32("hello") == murmur3_32(b"hello")
        # stability across calls (shard-assignment contract)
        assert murmur3_32("word", 7) == murmur3_32("word", 7)

    def test_scaler_uint8_fast_path(self):
        from deeplearning4j_tpu.datasets import (DataSet,
                                                 ImagePreProcessingScaler)
        r = np.random.RandomState(2)
        img = r.randint(0, 256, (3, 4, 4, 1), np.uint8)
        ds = DataSet(img, np.zeros((3, 2), np.float32))
        ImagePreProcessingScaler().transform(ds)
        np.testing.assert_allclose(ds.features,
                                   img.astype(np.float32) / 255.0,
                                   rtol=0, atol=1e-7)

    def test_standardize_uint8_fast_path(self):
        from deeplearning4j_tpu.datasets import DataSet, NormalizerStandardize
        r = np.random.RandomState(3)
        imgs = r.randint(0, 256, (8, 4, 4, 3), np.uint8)
        norm = NormalizerStandardize()
        norm.fit(DataSet(imgs.astype(np.float32), np.zeros((8, 1))))
        ds = DataSet(imgs, np.zeros((8, 1), np.float32))
        norm.transform(ds)
        want = (imgs.astype(np.float32) - norm.mean) / norm.std
        np.testing.assert_allclose(ds.features, want, rtol=1e-5, atol=1e-4)


class TestRequireNative:
    def test_require_native_raises_when_lib_missing(self, monkeypatch):
        """Under the gate (DL4J_TPU_REQUIRE_NATIVE=1) a missing native lib
        is a hard error, never a silent numpy fallback."""
        import pytest

        from deeplearning4j_tpu.native_ops import threshold as T

        monkeypatch.setattr(T, "_LIB", None)
        monkeypatch.setattr(T, "_TRIED", True)
        monkeypatch.setenv("DL4J_TPU_REQUIRE_NATIVE", "1")
        with pytest.raises(RuntimeError, match="REQUIRE_NATIVE"):
            T._get_lib()

    def test_missing_lib_falls_back_without_flag(self, monkeypatch):
        from deeplearning4j_tpu.native_ops import threshold as T

        monkeypatch.setattr(T, "_LIB", None)
        monkeypatch.setattr(T, "_TRIED", True)
        monkeypatch.delenv("DL4J_TPU_REQUIRE_NATIVE", raising=False)
        assert T._get_lib() is None  # caller uses the numpy path


class TestNativeLoaderFreshness:
    """The loader builds from what git would commit: native/build is
    git-ignored and survives checkouts, so an old library beside newer
    sources must never be loaded."""

    def _fake_native(self, tmp_path, monkeypatch, so_age, src_age):
        import os

        from deeplearning4j_tpu.native_ops import threshold as T

        (tmp_path / "build").mkdir()
        so = tmp_path / "build" / "libdl4j_tpu_native.so"
        so.write_bytes(b"")
        src = tmp_path / "threshold_codec.cpp"
        src.write_text("// source")
        os.utime(so, (so_age, so_age))
        os.utime(src, (src_age, src_age))
        monkeypatch.setattr(T, "_NATIVE_DIR", str(tmp_path))
        return T, str(so)

    def test_library_older_than_a_source_is_stale(self, tmp_path,
                                                  monkeypatch):
        T, so = self._fake_native(tmp_path, monkeypatch, so_age=1000,
                                  src_age=2000)
        assert T._stale(so)

    def test_library_newer_than_every_source_is_fresh(self, tmp_path,
                                                      monkeypatch):
        T, so = self._fake_native(tmp_path, monkeypatch, so_age=2000,
                                  src_age=1000)
        assert not T._stale(so)

    def test_failed_rebuild_is_logged_and_not_loaded(self, tmp_path,
                                                     monkeypatch, caplog):
        import subprocess

        T, _ = self._fake_native(tmp_path, monkeypatch, so_age=1000,
                                 src_age=2000)

        def boom(cmd, **kw):
            raise subprocess.CalledProcessError(2, cmd,
                                                stderr=b"no compiler")

        monkeypatch.setattr(T.subprocess, "run", boom)
        with caplog.at_level("WARNING", logger=T.__name__):
            assert T._build_and_load() is None  # stale .so NOT loaded
        assert "no compiler" in caplog.text
