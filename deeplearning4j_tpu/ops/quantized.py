"""Int8 quantized matmul — the cheap high-QPS serving path.

Seeded from the ``ops/compression.py`` design (threshold codec: scale-based
encode with a residual, static shapes under jit): weights are quantized
ONCE offline to symmetric int8 with a per-output-channel f32 scale
(``quantize_int8``), activations are quantized dynamically per row at call
time, and ``matmul_int8`` runs the int8×int8 dot with wide accumulation
before de-scaling back to the activation dtype:

    w_q, w_scale = quantize_int8(w, axis=0)          # offline, per column
    y = matmul_int8(x, w_q, w_scale)                 # serving hot path

* **generic impl**: XLA int8 ``dot_general`` with an int32 accumulator
  (exact), de-scaled in f32 — runs anywhere.
* **Pallas TPU helper**: the ``pallas_matmul`` block layout with int8 MXU
  tiles and an f32 VMEM accumulator; the per-row/per-column de-scale is the
  epilogue, so the int32/f32 intermediate never reaches HBM. int8 tiles
  want (32, 128) alignment (pallas_guide.md tiling table) — the usable()
  gate and the tuned block sizes (``ops/tuning.py``) enforce it.
* **gradients**: straight-through on the activation quantization — the
  backward is ``g @ dequantize(w).T``, exactly the f32 matmul backward
  against the dequantized weights (weights are frozen int8 at serving
  time; no weight gradient is defined).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# shared divisibility-first block picker (one definition; drift between
# per-module copies is how alignment fixes get lost)
from deeplearning4j_tpu.ops.pallas_matmul import _pick_block
from deeplearning4j_tpu.ops.registry import op, pallas_interpret

_QMAX = 127.0


@op("quantize_int8")
def quantize_int8(x, *, axis=None):
    """Symmetric int8 quantization: ``(q, scale)`` with
    ``x ≈ q * scale``. ``axis``: reduction axis/axes the scale is SHARED
    over (None = one per-tensor scale; ``axis=0`` on a (K, N) weight gives
    one scale per output column — the matmul_int8 layout)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-12).astype(jnp.float32) / _QMAX
    q = jnp.clip(jnp.round(x / scale.astype(x.dtype)), -_QMAX, _QMAX) \
        .astype(jnp.int8)
    return q, scale


@op("dequantize_int8")
def dequantize_int8(q, scale):
    """Densify: ``q * scale`` in f32 (broadcasts the saved scale layout)."""
    return q.astype(jnp.float32) * scale


def _row_quantize(x):
    """Dynamic per-row activation quantization ((…, K) -> int8 + (…, 1)
    row scales), inlined on the hot path (axis=-1 keepdims layout)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12).astype(jnp.float32) / _QMAX
    q = jnp.clip(jnp.round(x / scale.astype(x.dtype)), -_QMAX, _QMAX) \
        .astype(jnp.int8)
    return q, scale


def _matmul_int8_raw(x, w_q, w_scale):
    xq, xs = _row_quantize(x)
    acc = jax.lax.dot_general(
        xq, w_q, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * xs * w_scale.reshape(1, -1)
    return y.astype(x.dtype)


@jax.custom_vjp
def _mm8(x, w_q, w_scale):
    return _matmul_int8_raw(x, w_q, w_scale)


def _mm8_fwd(x, w_q, w_scale):
    return _mm8(x, w_q, w_scale), (x, w_q, w_scale)


def _mm8_bwd(res, g):
    x, w_q, w_scale = res
    w_deq = w_q.astype(jnp.float32) * w_scale.reshape(1, -1)
    dx = jnp.matmul(g.astype(jnp.float32),
                    w_deq.T).astype(x.dtype)
    # int8 weights take float0 cotangents (non-differentiable integers);
    # the frozen serving scale gets a symbolic zero
    return (dx, np.zeros(w_q.shape, jax.dtypes.float0),
            jnp.zeros_like(w_scale))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


@op("matmul_int8")
def matmul_int8(x, w_q, w_scale):
    """``x @ dequantize(w_q, w_scale)`` computed in int8.

    x: (…, M, K) float; w_q: (K, N) int8; w_scale: (N,) f32 per-column.
    Activations quantize dynamically per row (straight-through for
    gradients); the int8×int8 dot accumulates wide and de-scales by
    ``row_scale · column_scale`` — the compression.py scale discipline
    applied to the MXU."""
    return _mm8(x, w_q, w_scale)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # int8 MXU tiles accumulate in int32 (exact, like the generic impl —
    # Mosaic refuses an int8 x int8 dot that asks for a float result);
    # the cast to f32 happens once, before the epilogue de-scale
    acc_ref[:] += jax.lax.dot_general(
        xq_ref[0], wq_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _():
        y = acc_ref[:].astype(jnp.float32) * xs_ref[0] * ws_ref[0]
        o_ref[0] = y.astype(o_ref.dtype)


def matmul_int8_pallas(x, w_q, w_scale, *, block_m: int = 0,
                       block_n: int = 0, block_k: int = 0, interpret=None):
    """Pallas forward for matmul_int8: quantize rows via XLA, then one
    blocked int8 MXU kernel with the de-scale epilogue in VMEM."""
    interpret = pallas_interpret(interpret)
    from deeplearning4j_tpu.ops import tuning

    lead = x.shape[:-2] if x.ndim > 2 else ()
    m = 1
    for d in x.shape[:-1]:
        m *= d
    k_dim = x.shape[-1]
    n = w_q.shape[1]
    bucket = tuning.bucket_mkn(m, k_dim, n)
    bm = block_m or tuning.tuned_block(
        "matmul_int8", "block_m", m, bucket,
        lambda s: _pick_block(s, (256, 128, 64, 32)))
    bn = block_n or tuning.tuned_block(
        "matmul_int8", "block_n", n, bucket,
        lambda s: _pick_block(s, (256, 128)))
    bk = block_k or tuning.tuned_block(
        "matmul_int8", "block_k", k_dim, bucket,
        lambda s: _pick_block(s, (512, 256, 128)))
    if m % bm or n % bn or k_dim % bk:
        raise ValueError(f"shape ({m},{k_dim})x({k_dim},{n}) not divisible "
                         f"by blocks ({bm},{bk},{bn})")
    xq, xs = _row_quantize(x.reshape(m, k_dim))
    grid = (m // bm, n // bn, k_dim // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2]),
        out_shape=jax.ShapeDtypeStruct((1, m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((1, bm, 1), lambda i, j, k: (0, i, 0)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, k: (0, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq[None], xs[None], w_q[None], w_scale.reshape(1, n))
    return out[0].reshape(lead + (x.shape[-2], n))


@jax.custom_vjp
def _mm8_pl(x, w_q, w_scale):
    return matmul_int8_pallas(x, w_q, w_scale)


def _mm8_pl_fwd(x, w_q, w_scale):
    return _mm8_pl(x, w_q, w_scale), (x, w_q, w_scale)


_mm8_pl.defvjp(_mm8_pl_fwd, _mm8_bwd)  # same XLA backward as the generic


def matmul_int8_helper(x, w_q, w_scale):
    """The registered TPU platform impl: differentiable Pallas forward."""
    return _mm8_pl(x, w_q, w_scale)


def _usable(x, w_q, w_scale, **kw):
    """PlatformHelper::isUsable: 2-D/3-D float x, int8 (K, N) weights,
    Mosaic int8 tile alignment, and the measured min-rows crossover."""
    if getattr(x, "ndim", 0) not in (2, 3) or getattr(w_q, "ndim", 0) != 2:
        return False
    dt = getattr(x, "dtype", None)
    if dt is None or not jnp.issubdtype(dt, jnp.floating):
        return False
    if getattr(w_q, "dtype", None) != jnp.int8:
        return False
    m = 1
    for d in x.shape[:-1]:
        m *= d
    k_dim, n = w_q.shape
    from deeplearning4j_tpu.ops import tuning

    if m < int(tuning.tuned("matmul_int8", "pallas_min_m", 32)):
        return False
    return m % 32 == 0 and k_dim % 128 == 0 and n % 128 == 0


def _check_matmul_int8():
    """Validation case (ops.validation ratchet): scale round-trip vs a
    numpy int8 oracle, generic vs Pallas interpret, quantize/dequantize
    round-trip error bounded by the scale quantum."""
    r = np.random.RandomState(17)
    x = r.randn(32, 128).astype(np.float32)
    w = (r.randn(128, 128) * 128 ** -0.5).astype(np.float32)

    wq, ws = quantize_int8.fn(jnp.asarray(w), axis=0)
    # quantize/dequantize round trip: error <= scale/2 per entry
    w_rt = np.asarray(dequantize_int8.fn(wq, ws))
    np.testing.assert_array_less(
        np.abs(w_rt - w),
        np.broadcast_to(np.asarray(ws) / 2 + 1e-9, w.shape))

    # numpy oracle of the exact same quantized math
    xs = np.maximum(np.abs(x).max(-1, keepdims=True), 1e-12) / 127.0
    xq = np.clip(np.round(x / xs), -127, 127).astype(np.int8)
    want = (xq.astype(np.int64) @ np.asarray(wq).astype(np.int64)) \
        .astype(np.float32) * xs * np.asarray(ws).reshape(1, -1)
    got = matmul_int8.fn(jnp.asarray(x), wq, ws)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    got_pl = matmul_int8_pallas(jnp.asarray(x), wq, ws, block_m=32,
                                block_k=128, block_n=128)
    np.testing.assert_allclose(np.asarray(got_pl), want, rtol=1e-5,
                               atol=1e-6)


def _check_quantize_round_trip():
    """Validation case (ops.validation ratchet): symmetric quantize /
    dequantize round trip vs a numpy oracle, per-tensor and per-axis —
    error bounded by half the scale quantum, extremes map to ±127."""
    r = np.random.RandomState(23)
    x = r.randn(8, 16).astype(np.float32)
    for axis in (None, 0, 1):
        q, s = quantize_int8.fn(jnp.asarray(x), axis=axis)
        qn, sn = np.asarray(q), np.asarray(s)
        amax = np.abs(x).max() if axis is None else \
            np.abs(x).max(axis=axis, keepdims=True)
        np.testing.assert_allclose(sn, np.maximum(amax, 1e-12) / 127.0,
                                   rtol=1e-6)
        assert qn.dtype == np.int8 and np.abs(qn).max() <= 127
        back = np.asarray(dequantize_int8.fn(q, s))
        assert (np.abs(back - x) <= np.broadcast_to(sn / 2 + 1e-9,
                                                    x.shape)).all()


def register_platform_quantized() -> None:
    """Install the Pallas int8 kernel as the TPU platform override for
    matmul_int8 (cuDNN PlatformHelper pattern)."""
    from deeplearning4j_tpu.ops import validation as _validation
    from deeplearning4j_tpu.ops.registry import registry

    reg = registry()
    desc = reg.get("matmul_int8")
    if "tpu" not in desc.platform_impls:
        reg.register_platform("matmul_int8", "tpu", matmul_int8_helper,
                              _usable)
        _validation.add_case("matmul_int8", _check_matmul_int8)
        _validation.add_case("quantize_int8", _check_quantize_round_trip)
        _validation.add_case("dequantize_int8", _check_quantize_round_trip)
