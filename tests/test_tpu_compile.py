"""The main path's Pallas kernels, compiled by the installed TPU compiler.

Interpret mode (all the rest of the suite) never legalises for Mosaic, so a
kernel can pass every CPU test and still be refused by the chip's compiler.
Each case here lowers one kernel with ``interpret=False`` at the width
``chip_smoke.py`` runs it (BERT-base b16 x s512, GPT-2-small with 16 slots
and 65 pages of 16) for a DESCRIBED ``v5e:2x2`` device — no chip attached,
nothing executes — and asserts the Mosaic call is in the compiled text.

The topology is described inside the module-scoped fixture only: one
process at a time may load the TPU library, and pytest-xdist imports this
file in every worker, so nothing here touches ``topologies`` at import.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.nn.updater import UPDATERS
from deeplearning4j_tpu.ops.pallas_attention import (
    _paged_decode_call, flash_attention)
from deeplearning4j_tpu.ops.pallas_layernorm import fused_layer_norm_pallas
from deeplearning4j_tpu.ops.pallas_matmul import fused_matmul_bias_act_pallas
from deeplearning4j_tpu.ops.pallas_updater import fused_updater_helper
from deeplearning4j_tpu.ops.quantized import matmul_int8_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises without libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for an unattached chip can be written to the
    # persistent cache but never read back: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_mosaic(text):
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_gpt2_small(one_chip, dtype):
    slots, heads, dh, page, pages_per_seq = 16, 12, 64, 16, 65
    n_pages = slots * pages_per_seq + 1
    kv = ((n_pages, page, heads, dh), dtype)
    text = _compiled_text(
        one_chip, functools.partial(_paged_decode_call, interpret=False),
        ((slots, heads, dh), dtype), kv, kv,
        ((slots, pages_per_seq), jnp.int32), ((slots,), jnp.int32))
    _assert_mosaic(text)


@pytest.mark.parametrize("bh,t,causal,rate", [
    (8, 4096, True, 0.0),      # the flash_min_t crossover shape
    (192, 512, False, 0.1),    # BERT-base b16 x 12 heads, in-kernel dropout
])
def test_flash_attention_fwd_and_grad(one_chip, bh, t, causal, rate):
    def loss(q, k, v, seed):
        out = flash_attention(q, k, v, None, seed if rate else None, None,
                              causal, None, None, False, rate)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((bh, t, 64), jnp.bfloat16)
    text = _compiled_text(
        one_chip, jax.value_and_grad(loss, argnums=(0, 1, 2)),
        qkv, qkv, qkv, ((1, 1), jnp.int32))
    _assert_mosaic(text)


@pytest.mark.parametrize("rows", [8192, 16])
def test_fused_matmul_bias_gelu(one_chip, rows):
    text = _compiled_text(
        one_chip,
        functools.partial(fused_matmul_bias_act_pallas, activation="gelu",
                          interpret=False),
        ((rows, 768), jnp.bfloat16), ((768, 3072), jnp.bfloat16),
        ((3072,), jnp.bfloat16))
    _assert_mosaic(text)


@pytest.mark.parametrize("lead,activation", [((16, 512), "gelu"),
                                             ((16, 512), "none"),
                                             ((16,), "none")])
def test_fused_layer_norm(one_chip, lead, activation):
    text = _compiled_text(
        one_chip,
        functools.partial(fused_layer_norm_pallas, activation=activation,
                          interpret=False),
        (lead + (768,), jnp.bfloat16), ((768,), jnp.bfloat16),
        ((768,), jnp.bfloat16))
    _assert_mosaic(text)


@pytest.mark.parametrize("kind", sorted(UPDATERS))
def test_fused_updater_every_kind(one_chip, kind):
    """All eleven kinds share one kernel body; the Adam family traced
    ``beta**t`` (``math.powf``, which Mosaic cannot legalise) inside it
    until the per-step scalars moved out to ``Updater.scalars``."""
    leaf = ((768, 3072), jnp.float32)
    n_state = len(UPDATERS[kind]().init_state(jnp.zeros((), jnp.float32)))
    text = _compiled_text(
        one_chip,
        functools.partial(fused_updater_helper, kind=kind, interpret=False),
        leaf, leaf, ((), jnp.float32), ((), jnp.int32), *([leaf] * n_state))
    _assert_mosaic(text)


def test_fused_updater_bf16_embedding_leaf(one_chip):
    """BERT-base's largest leaf (word embeddings) as bf16 Adam trains it:
    30522 x 768 does not divide into (rows, 128) blocks, so the pad path
    and the bf16 store casts are both in the kernel."""
    leaf = ((30522, 768), jnp.bfloat16)
    text = _compiled_text(
        one_chip,
        functools.partial(fused_updater_helper, kind="Adam",
                          interpret=False),
        leaf, leaf, ((), jnp.float32), ((), jnp.int32), leaf, leaf)
    _assert_mosaic(text)


@pytest.mark.parametrize("rows", [8192, 32])
def test_matmul_int8(one_chip, rows):
    text = _compiled_text(
        one_chip, functools.partial(matmul_int8_pallas, interpret=False),
        ((rows, 768), jnp.bfloat16), ((768, 3072), jnp.int8),
        ((3072,), jnp.float32))
    _assert_mosaic(text)
