"""Kernel autotuner + measured dispatch (ops/tuning.py, docs/KERNELS.md).

Covers the tuning-table serde/merge/fallback contract, the tuned() read
path every dispatch site uses, the dispatch-counter family, and —
per-tuned-op — that resolve picks XLA below and Pallas above the measured
threshold (the ISSUE 9 acceptance criterion, asserted via the
dl4j_tpu_helper_dispatch_total counters)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import deeplearning4j_tpu.ops  # noqa: F401 - registers catalog + helpers
from deeplearning4j_tpu import observe
from deeplearning4j_tpu.environment import environment
from deeplearning4j_tpu.ops import tuning
from deeplearning4j_tpu.ops.registry import registry


@pytest.fixture
def tuning_sandbox(tmp_path, monkeypatch):
    """Point the tuning cache at a per-test dir; restore memoized tables on
    exit so a test-written table never leaks into other tests."""
    monkeypatch.setenv(tuning.ENV_DIR, str(tmp_path))
    tuning.reset_tables()
    yield tmp_path
    monkeypatch.undo()
    tuning.reset_tables()


def _write_table(tmp_path, entries, kind="cpu"):
    t = tuning.TuningTable(device_kind=kind, entries=entries)
    t.save(os.path.join(str(tmp_path), f"{kind}.json"))
    tuning.reset_tables()
    return t


@pytest.fixture
def pallas_mode():
    env = environment()
    old = env.helper_mode
    env.helper_mode = "pallas"  # platform-table resolution on CPU
    yield env
    env.helper_mode = old


def _dispatch_delta(fn):
    before = observe.dispatch_summary()
    out = fn()
    after = observe.dispatch_summary()
    return out, {k: after.get(k, 0) - before.get(k, 0)
                 for k in after if after.get(k, 0) != before.get(k, 0)}


class TestTableSerde:
    def test_round_trip(self, tmp_path):
        t = tuning.TuningTable(device_kind="cpu")
        t.set("dot_product_attention", "flash_min_t", 256)
        t.set_block("matmul_int8", "m256_k512_n512", "block_m", 128)
        path = t.save(str(tmp_path / "cpu.json"))
        back = tuning.TuningTable.load(path)
        assert back.device_kind == "cpu"
        assert back.get("dot_product_attention", "flash_min_t") == 256
        assert back.get_block("matmul_int8", "m256_k512_n512",
                              "block_m") == 128

    def test_merge_deep(self):
        a = tuning.TuningTable("cpu", {
            "op": {"thresh": 1, "blocks": {"t64": {"block_q": 8}}}})
        b = tuning.TuningTable("cpu", {
            "op": {"thresh": 2, "blocks": {"t64": {"block_k": 16},
                                           "t128": {"block_q": 32}}}})
        a.merge(b)
        assert a.get("op", "thresh") == 2  # other wins
        assert a.get_block("op", "t64", "block_q") == 8   # preserved
        assert a.get_block("op", "t64", "block_k") == 16  # merged in
        assert a.get_block("op", "t128", "block_q") == 32

    def test_rejects_wrong_schema(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"schema": "nope", "entries": {}}))
        with pytest.raises(ValueError):
            tuning.TuningTable.load(str(p))

    def test_corrupt_cache_falls_back_to_defaults(self, tuning_sandbox):
        # three corruption flavors: unparsable, wrong schema, bad entries
        (tuning_sandbox / "cpu.json").write_text("{not json")
        t = tuning.active_table("cpu")
        assert t.get("dot_product_attention", "flash_min_t") == 4096
        tuning.reset_tables()
        (tuning_sandbox / "cpu.json").write_text(
            json.dumps({"schema": "v0", "entries": {}}))
        assert tuning.active_table("cpu").get(
            "dot_product_attention", "flash_min_t") == 4096
        tuning.reset_tables()
        (tuning_sandbox / "cpu.json").write_text(
            json.dumps({"schema": tuning.SCHEMA, "entries": {"x": 3}}))
        assert tuning.active_table("cpu").get(
            "dot_product_attention", "flash_min_t") == 4096

    def test_malformed_blocks_falls_back_not_crashes(self, tuning_sandbox):
        # schema-valid but malformed: "blocks": null (a hand-merge typo)
        # must land in the warn-once fallback, not crash every tuned() read
        (tuning_sandbox / "cpu.json").write_text(json.dumps({
            "schema": tuning.SCHEMA, "device_kind": "cpu",
            "entries": {"fused_layer_norm": {"blocks": None}}}))
        tuning.reset_tables()
        assert tuning.tuned("dot_product_attention", "flash_min_t") == 4096
        (tuning_sandbox / "cpu.json").write_text(json.dumps({
            "schema": tuning.SCHEMA, "device_kind": "cpu",
            "entries": {"op": {"blocks": {"t64": 512}}}}))  # bucket->scalar
        tuning.reset_tables()
        assert tuning.tuned("dot_product_attention", "flash_min_t") == 4096

    def test_cache_overlays_default(self, tuning_sandbox):
        _write_table(tuning_sandbox,
                     {"dot_product_attention": {"flash_min_t": 99}})
        assert tuning.tuned("dot_product_attention", "flash_min_t") == 99
        # untouched defaults still visible through the overlay
        assert tuning.tuned("fused_updater_step", "min_size") == 65536

    def test_bucket_beats_op_level(self, tuning_sandbox):
        _write_table(tuning_sandbox, {"op": {
            "block_q": 1, "blocks": {"t64": {"block_q": 7}}}})
        assert tuning.tuned("op", "block_q", bucket="t64") == 7
        assert tuning.tuned("op", "block_q", bucket="t128") == 1
        assert tuning.tuned("op", "missing", 5, bucket="t64") == 5


class TestBuckets:
    def test_pow2(self):
        assert [tuning.pow2_bucket(n) for n in (1, 2, 3, 63, 64, 65)] == \
            [1, 2, 4, 64, 64, 128]

    def test_labels(self):
        assert tuning.bucket_t(4097) == "t8192"
        assert tuning.bucket_mkn(100, 512, 513) == "m128_k512_n1024"
        assert tuning.bucket_rows(9) == "r16"

    def test_tuned_block_divisibility_fallback(self, tuning_sandbox):
        _write_table(tuning_sandbox, {"op": {
            "blocks": {"t64": {"block_q": 48}}}})
        # 48 does not divide 64 -> fallback runs
        assert tuning.tuned_block("op", "block_q", 64, "t64",
                                  lambda s: 32) == 32
        # 48 divides 96 -> tuned value wins
        assert tuning.tuned_block("op", "block_q", 96, "t64",
                                  lambda s: 32) == 48


class TestAutotune:
    def test_smoke_subset_saves_and_is_live(self, tuning_sandbox):
        table, report = tuning.autotune(ops=["fused_updater_step"],
                                        smoke=True)
        assert report.ops == ["fused_updater_step"]
        assert report.measurements > 0
        assert os.path.exists(report.table_path)
        loaded = tuning.TuningTable.load(report.table_path)
        assert loaded.get("fused_updater_step", "min_size") is not None
        # autotune(save=True) reset the memoized readers: live immediately
        assert tuning.tuned("fused_updater_step", "min_size") == \
            loaded.get("fused_updater_step", "min_size")

    def test_subset_tune_preserves_other_ops_entries(self, tuning_sandbox):
        """A --ops subset re-tune must refresh only what it measured — not
        clobber previously measured entries for other ops."""
        _write_table(tuning_sandbox,
                     {"fused_layer_norm": {"min_rows": 123}})
        tuning.autotune(ops=["fused_updater_step"], smoke=True)
        saved = tuning.TuningTable.load(str(tuning_sandbox / "cpu.json"))
        assert saved.get("fused_layer_norm", "min_rows") == 123  # kept
        assert saved.get("fused_updater_step", "min_size") is not None

    def test_aot_time_measures(self):
        sec = tuning.aot_time(lambda x: x * 2.0,
                              (jnp.ones((8, 8), jnp.float32),), iters=2,
                              reps=1)
        assert sec > 0.0

    def test_tuning_telemetry(self, tuning_sandbox):
        c = observe.metrics().counter("dl4j_tpu_tuning_runs_total",
                                     op="fused_updater_step")
        before = c.value
        tuning.autotune(ops=["fused_updater_step"], smoke=True, save=False)
        assert c.value == before + 1


class TestFlashMinTCache:
    """Round-9 bugfix: flash_min_t parses once per distinct env value and
    logs the invalid-value warning once, not per resolve call."""

    def test_env_changes_stay_live(self, monkeypatch):
        from deeplearning4j_tpu.ops.pallas_attention import (
            flash_min_t, reset_flash_min_t_cache)

        reset_flash_min_t_cache()
        monkeypatch.delenv("DL4J_TPU_FLASH_MIN_T", raising=False)
        assert flash_min_t() == 4096
        monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "123")
        assert flash_min_t() == 123

    def test_invalid_value_warns_once(self, monkeypatch, caplog):
        import logging

        from deeplearning4j_tpu.ops import pallas_attention as pa

        pa.reset_flash_min_t_cache()
        monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "junk")
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu.ops.pallas_attention"):
            for _ in range(5):
                assert pa.flash_min_t() == 4096
        warns = [r for r in caplog.records
                 if "DL4J_TPU_FLASH_MIN_T" in r.getMessage()]
        assert len(warns) == 1

    def test_tuned_table_feeds_threshold(self, tuning_sandbox, monkeypatch):
        from deeplearning4j_tpu.ops import pallas_attention as pa

        monkeypatch.delenv("DL4J_TPU_FLASH_MIN_T", raising=False)
        _write_table(tuning_sandbox,
                     {"dot_product_attention": {"flash_min_t": 48}})
        assert pa.flash_min_t() == 48
        # env override still wins over the measured table
        monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "96")
        assert pa.flash_min_t() == 96


class TestMeasuredDispatch:
    """Both sides of the tuned threshold for EVERY tuned op, asserted via
    impl identity AND the dispatch-counter family."""

    def test_attention_flash_min_t(self, tuning_sandbox, pallas_mode,
                                   monkeypatch):
        monkeypatch.delenv("DL4J_TPU_FLASH_MIN_T", raising=False)
        _write_table(tuning_sandbox,
                     {"dot_product_attention": {"flash_min_t": 64}})
        desc = registry().get("dot_product_attention")
        lo = jnp.zeros((2, 32, 16), jnp.float32)
        hi = jnp.zeros((2, 128, 16), jnp.float32)
        below, d1 = _dispatch_delta(lambda: desc.resolve(lo, lo, lo))
        above, d2 = _dispatch_delta(lambda: desc.resolve(hi, hi, hi))
        assert below is desc.fn
        assert above is desc.platform_impls["tpu"]
        assert d1.get("dot_product_attention/generic/not_usable") == 1
        assert d2.get("dot_product_attention/tpu/usable") == 1

    def test_fused_matmul_pallas_min_m(self, tuning_sandbox, pallas_mode):
        _write_table(tuning_sandbox,
                     {"fused_matmul_bias_act": {"pallas_min_m": 64}})
        desc = registry().get("fused_matmul_bias_act")
        w = jnp.zeros((128, 128), jnp.float32)
        below, d1 = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((32, 128), jnp.float32), w))
        above, d2 = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((64, 128), jnp.float32), w))
        assert below is desc.fn
        assert above is not desc.fn
        assert d1.get("fused_matmul_bias_act/generic/not_usable") == 1
        assert d2.get("fused_matmul_bias_act/tpu/usable") == 1

    def test_layernorm_min_rows(self, tuning_sandbox, pallas_mode):
        _write_table(tuning_sandbox,
                     {"fused_layer_norm": {"min_rows": 32}})
        desc = registry().get("fused_layer_norm")
        g = jnp.ones((128,), jnp.float32)
        below, d1 = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((16, 128), jnp.float32), g))
        above, d2 = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((32, 128), jnp.float32), g))
        assert below is desc.fn
        assert above is not desc.fn
        assert d1.get("fused_layer_norm/generic/not_usable") == 1
        assert d2.get("fused_layer_norm/tpu/usable") == 1

    def test_updater_min_size(self, tuning_sandbox, pallas_mode):
        _write_table(tuning_sandbox,
                     {"fused_updater_step": {"min_size": 1024}})
        desc = registry().get("fused_updater_step")
        lr = jnp.float32(1e-2)
        step = jnp.float32(0.0)

        def args(n):
            z = jnp.zeros((n,), jnp.float32)
            return (z, z, lr, step, z)  # Nesterovs: one state leaf (v)

        below, d1 = _dispatch_delta(
            lambda: desc.resolve(*args(512), kind="Nesterovs"))
        above, d2 = _dispatch_delta(
            lambda: desc.resolve(*args(1024), kind="Nesterovs"))
        assert below is desc.fn
        assert above is not desc.fn
        assert d1.get("fused_updater_step/generic/not_usable") == 1
        assert d2.get("fused_updater_step/tpu/usable") == 1

    def test_int8_pallas_min_m(self, tuning_sandbox, pallas_mode):
        _write_table(tuning_sandbox,
                     {"matmul_int8": {"pallas_min_m": 64}})
        desc = registry().get("matmul_int8")
        wq = jnp.zeros((128, 128), jnp.int8)
        ws = jnp.ones((128,), jnp.float32)
        below, d1 = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((32, 128), jnp.float32), wq, ws))
        above, d2 = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((64, 128), jnp.float32), wq, ws))
        assert below is desc.fn
        assert above is not desc.fn
        assert d1.get("matmul_int8/generic/not_usable") == 1
        assert d2.get("matmul_int8/tpu/usable") == 1

    def test_paged_decode_min_pages(self, tuning_sandbox, pallas_mode):
        _write_table(tuning_sandbox,
                     {"paged_decode_attention": {"min_pages": 4}})
        desc = registry().get("paged_decode_attention")
        q = jnp.zeros((2, 2, 64), jnp.float32)
        kv = jnp.zeros((1, 2, 8, 8, 128), jnp.float32)
        sl = jnp.zeros((2,), jnp.int32)

        def pt(pages):
            return jnp.zeros((2, pages), jnp.int32)

        below, d1 = _dispatch_delta(
            lambda: desc.resolve(q, kv, pt(2), sl, layer=0))
        above, d2 = _dispatch_delta(
            lambda: desc.resolve(q, kv, pt(4), sl, layer=0))
        assert below is desc.fn
        assert above is not desc.fn
        assert d1.get("paged_decode_attention/generic/not_usable") == 1
        assert d2.get("paged_decode_attention/tpu/usable") == 1

    def test_helperless_ops_not_counted(self):
        desc = registry().get("layer_norm")  # no platform impls
        _, delta = _dispatch_delta(
            lambda: desc.resolve(jnp.zeros((4, 8)), jnp.ones((8,))))
        assert not any(k.startswith("layer_norm/") for k in delta)

    def test_forced_xla_counted(self, tuning_sandbox):
        env = environment()
        old = env.helper_mode
        env.helper_mode = "xla"
        try:
            desc = registry().get("fused_layer_norm")
            impl, delta = _dispatch_delta(
                lambda: desc.resolve(jnp.zeros((32, 128), jnp.float32),
                                     jnp.ones((128,), jnp.float32)))
        finally:
            env.helper_mode = old
        assert impl is desc.fn
        assert delta.get("fused_layer_norm/generic/forced_xla") == 1


class TestObserveSurface:
    def test_dispatch_in_summary(self, tuning_sandbox, pallas_mode):
        _write_table(tuning_sandbox,
                     {"fused_layer_norm": {"min_rows": 8}})
        desc = registry().get("fused_layer_norm")
        desc.resolve(jnp.zeros((32, 128), jnp.float32),
                     jnp.ones((128,), jnp.float32))
        s = observe.summary()
        assert "dispatch" in s
        assert any(k.startswith("fused_layer_norm/") for k in s["dispatch"])


class TestSweepFragments:
    """tools/bench_* sweep tools emit mergeable dl4j_tpu_tuning_v1
    fragments (the schema contract; the sweeps themselves need a chip)."""

    def test_fragment_merges_into_default(self, tuning_sandbox):
        frag = tuning.TuningTable(device_kind="cpu")
        frag.set("dot_product_attention", "flash_min_t", 2048)
        frag.set_block("fused_layer_norm", "r4096", "block_rows", 512)
        path = frag.save(str(tuning_sandbox / "fragment.json"))
        base = tuning.active_table("cpu")
        merged = tuning.TuningTable(base.device_kind,
                                    json.loads(json.dumps(base.entries)))
        merged.merge(tuning.TuningTable.load(path))
        assert merged.get("dot_product_attention", "flash_min_t") == 2048
        assert merged.get_block("fused_layer_norm", "r4096",
                                "block_rows") == 512
        # untouched entries survive the merge
        assert merged.get("fused_updater_step", "min_size") == 65536
