"""Flash-attention kernel tests — the two-backends-one-answer pattern
(SURVEY §5.2): Pallas kernel vs the generic XLA attention oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.pallas_attention import (
    flash_attention, flash_mha, _reference_attention, register_platform_attention,
)


def rand_qkv(bh=4, t=64, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(bh, t, d).astype(np.float32)),
            jnp.asarray(rng.randn(bh, t, d).astype(np.float32)),
            jnp.asarray(rng.randn(bh, t, d).astype(np.float32)))


class TestFlashAttention:
    def test_matches_reference(self):
        q, k, v = rand_qkv()
        out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(32), causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_causal_matches_reference(self):
        q, k, v = rand_qkv(t=32)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(32), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_non_divisible_seq_len(self):
        q, k, v = rand_qkv(t=50)  # not a multiple of block
        out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(32), causal=False)
        # zero-padded keys contribute exp(s) mass — guard: compare unpadded
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-4)

    def test_gradients_flow(self):
        q, k, v = rand_qkv(bh=2, t=16, d=16)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=8, block_k=8, interpret=True) ** 2)

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def ref_loss(q, k, v):
            return jnp.sum(_reference_attention(
                q, k, v, scale=1.0 / np.sqrt(16), causal=False) ** 2)

        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), rtol=1e-3, atol=1e-4)

    def test_flash_mha_wrapper(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(2, 24, 32).astype(np.float32))
        out = flash_mha(x, x, x, num_heads=4, interpret=True)
        assert out.shape == (2, 24, 32)

    def test_long_sequence_blocks(self):
        q, k, v = rand_qkv(bh=1, t=256, d=16, seed=3)
        out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(16), causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_platform_registration(self):
        from deeplearning4j_tpu.ops.registry import registry

        register_platform_attention()
        desc = registry().get("dot_product_attention")
        assert "tpu" in desc.platform_impls


    def test_key_padding_mask_matches_reference(self):
        q, k, v = rand_qkv(bh=3, t=40, d=16, seed=5)
        rng = np.random.RandomState(7)
        mask = jnp.asarray((rng.rand(3, 40) > 0.3).astype(np.float32))
        out = flash_attention(q, k, v, mask, block_q=16, block_k=16, interpret=True)
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(16),
                                   causal=False, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-4)

    def test_dropout_zero_rate_matches_reference(self):
        q, k, v = rand_qkv(bh=2, t=32, d=16, seed=11)
        seed = jnp.asarray([[5]], jnp.int32)
        out = flash_attention(q, k, v, None, seed, block_q=16, block_k=16,
                              interpret=True, dropout_rate=0.0)
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(16),
                                   causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_dropout_deterministic_and_unbiased(self):
        q, k, v = rand_qkv(bh=2, t=32, d=16, seed=13)
        seed = jnp.asarray([[42]], jnp.int32)
        kw = dict(block_q=16, block_k=16, interpret=True, dropout_rate=0.3)
        a = flash_attention(q, k, v, None, seed, **kw)
        b = flash_attention(q, k, v, None, seed, **kw)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = flash_attention(q, k, v, None, jnp.asarray([[43]], jnp.int32), **kw)
        assert not np.allclose(np.asarray(a), np.asarray(c))
        # E[dropout(attn)] over seeds ≈ no-dropout output
        ref = _reference_attention(q, k, v, scale=1.0 / np.sqrt(16),
                                   causal=False)
        outs = [np.asarray(flash_attention(
            q, k, v, None, jnp.asarray([[s]], jnp.int32), **kw))
            for s in range(64)]
        err = np.abs(np.mean(outs, axis=0) - np.asarray(ref)).max()
        assert err < 0.15, f"dropout mean deviates from expectation: {err}"

    def test_dropout_gradients_flow_and_match_forward_mask(self):
        # gradient of sum(out) wrt v for a fixed seed equals the jacobian of
        # the (linear-in-v) dropped attention — check against numeric diff
        q, k, v = rand_qkv(bh=1, t=16, d=8, seed=17)
        seed = jnp.asarray([[7]], jnp.int32)
        kw = dict(block_q=8, block_k=8, interpret=True, dropout_rate=0.25)

        def loss(v):
            return jnp.sum(flash_attention(q, k, v, None, seed, **kw))

        g = np.asarray(jax.grad(loss)(v))
        eps = 1e-3
        v_np = np.asarray(v)
        for idx in [(0, 3, 2), (0, 9, 5)]:
            dv = v_np.copy(); dv[idx] += eps
            up = float(loss(jnp.asarray(dv)))
            dv[idx] -= 2 * eps
            dn = float(loss(jnp.asarray(dv)))
            num = (up - dn) / (2 * eps)
            np.testing.assert_allclose(g[idx], num, rtol=2e-2, atol=1e-3)

    def test_dropout_requires_seed(self):
        q, k, v = rand_qkv(bh=1, t=16, d=8)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, interpret=True, dropout_rate=0.1)

    def test_masked_gradients_match_reference(self):
        q, k, v = rand_qkv(bh=2, t=24, d=16, seed=9)
        mask = jnp.asarray((np.arange(24)[None, :] < np.array([[20], [16]]))
                           .astype(np.float32))

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, mask, block_q=8, block_k=8,
                                           interpret=True) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(_reference_attention(
                q, k, v, scale=1.0 / np.sqrt(16), causal=False,
                kv_mask=mask) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        r = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestShapeAwareDispatch:
    """The registry must route dot_product_attention by kv length: XLA below
    the crossover, the Pallas helper at/above it. The threshold is
    DL4J_TPU_FLASH_MIN_T (default 4096: from the void 2026-07 rig's sweep,
    not re-measured on the v5e — ROADMAP.md D15), read at resolve time."""

    def _desc(self):
        from deeplearning4j_tpu.ops.registry import registry

        register_platform_attention()  # idempotent under `in reg` guard
        return registry().get("dot_product_attention")

    def _qkv(self, t, d=16):
        x = jnp.zeros((2, t, d), jnp.float32)
        return x, x, x

    def test_default_threshold(self, monkeypatch):
        from deeplearning4j_tpu.ops.pallas_attention import flash_min_t

        monkeypatch.delenv("DL4J_TPU_FLASH_MIN_T", raising=False)
        assert flash_min_t() == 4096
        monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "512")
        assert flash_min_t() == 512
        monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "junk")
        assert flash_min_t() == 4096

    def test_dispatch_both_sides_of_boundary(self, monkeypatch):
        from deeplearning4j_tpu.environment import environment

        desc = self._desc()
        env = environment()
        old = env.helper_mode
        env.helper_mode = "pallas"  # force platform-table resolution on CPU
        try:
            monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "64")
            below = desc.resolve(*self._qkv(t=63))
            at = desc.resolve(*self._qkv(t=64))
            above = desc.resolve(*self._qkv(t=128))
            assert below is desc.fn, "below threshold must fall back to XLA"
            assert at is desc.platform_impls["tpu"]
            assert above is desc.platform_impls["tpu"]
        finally:
            env.helper_mode = old

    def test_dropout_overrides_threshold(self, monkeypatch):
        """In-kernel dropout flips the crossover (the generic path pays a
        (T, T) HBM mask) — flash stays selected below the threshold."""
        from deeplearning4j_tpu.environment import environment

        desc = self._desc()
        env = environment()
        old = env.helper_mode
        env.helper_mode = "pallas"
        try:
            monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "4096")
            q, k, v = self._qkv(t=32)
            got = desc.resolve(q, k, v, dropout_rate=0.1,
                               dropout_rng=jax.random.key(0))
            assert got is desc.platform_impls["tpu"]
        finally:
            env.helper_mode = old

    def test_causal_prefill_equivalence_across_dispatch(self):
        """The serving prefill calls the op with causal=True: both resolved
        impls must agree (1e-2/1e-5) so the dispatch threshold can never
        change generated text."""
        r = np.random.RandomState(4)
        q = jnp.asarray(r.randn(1, 2, 24, 16).astype(np.float32))
        k = jnp.asarray(r.randn(1, 2, 24, 16).astype(np.float32))
        v = jnp.asarray(r.randn(1, 2, 24, 16).astype(np.float32))
        mask = jnp.asarray((np.arange(24) < 20).astype(np.float32)
                           .reshape(1, 1, 1, 24))
        from deeplearning4j_tpu.ops.registry import registry

        desc = registry().get("dot_product_attention")
        generic = desc.fn(q, k, v, mask > 0.5, scaled=True, causal=True)
        flash = desc.platform_impls["tpu"](q, k, v, mask, scaled=True,
                                           causal=True)
        np.testing.assert_allclose(np.asarray(flash)[:, :, :20],
                                   np.asarray(generic)[:, :, :20],
                                   rtol=1e-2, atol=1e-5)
