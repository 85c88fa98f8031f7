"""Pallas TPU fused matmul epilogue — the platform helper for
``fused_matmul_bias_act`` (the optimizer's matmul+bias(+activation) fusion
target, docs/OPTIMIZER.md § Fusion tier).

XLA already fuses a bias add and an elementwise activation into the dot's
epilogue, but it materializes the f32 accumulator cast at the output dtype
boundary and (for bf16 policies) re-reads the result for the activation
pass when the consumer graph splits. This kernel makes the contract
explicit and unconditional: one MXU matmul in the operands' NATIVE dtype
with an f32 VMEM accumulator, bias and activation applied to the f32
accumulator in VMEM, ONE HBM write of the finished tile — the cuDNN
ScaleBiasActivation epilogue pattern (SURVEY §3.1).

Forward runs Pallas; backward is the hand-derived two-matmul VJP (the same
passes XLA emits for the unfused chain, computed via plain XLA dots —
matmul backward is already MXU-optimal, the fusion win is the forward
epilogue). Runs in interpret mode off-TPU so CPU tests exercise the same
code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.nn_ops import (
    FUSED_MATMUL_ACTIVATIONS, apply_fused_activation, fused_matmul_bias_act)
from deeplearning4j_tpu.ops.registry import pallas_interpret


def _pick_block(size: int, candidates=(512, 256, 128)) -> int:
    for c in candidates:
        if size % c == 0:
            return c
    return size


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int,
            activation: str, has_bias: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # native-dtype MXU dot with f32 accumulation (an up-front f32 cast
    # would force Mosaic's multi-pass f32 path — see pallas_attention._mm)
    acc_ref[:] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)

    @pl.when(k == n_k - 1)
    def _():
        y = acc_ref[:]                          # (bm, bn) f32
        if has_bias:
            y = y + b_ref[0]
        y = apply_fused_activation(y, activation)
        o_ref[0] = y.astype(o_ref.dtype)


def fused_matmul_bias_act_pallas(x, w, b=None, *, activation: str = "none",
                                 transpose_a: bool = False,
                                 transpose_b: bool = False,
                                 block_m: int = 0, block_n: int = 0,
                                 block_k: int = 0,
                                 interpret=None):
    """Pallas forward for act(x @ w + b); same contract as the generic.

    Accepts 2-D or 3-D ``x`` (leading batch folded into rows); transpose
    flags are rejected by the usable() gate but handled here defensively
    by materializing the transpose before the kernel."""
    interpret = pallas_interpret(interpret)
    if transpose_a:
        x = jnp.swapaxes(x, -1, -2)
    if transpose_b:
        w = jnp.swapaxes(w, -1, -2)
    lead = x.shape[:-2]
    m = 1
    for d in x.shape[:-1]:
        m *= d
    k_dim = x.shape[-1]
    n = w.shape[1]
    x2 = x.reshape(m, k_dim)
    # measured block sizes (ops/tuning.py) when the caller passed none —
    # validated against the real dims, falling back to the static pick
    from deeplearning4j_tpu.ops import tuning

    bucket = tuning.bucket_mkn(m, k_dim, n)
    bm = block_m or tuning.tuned_block(
        "fused_matmul_bias_act", "block_m", m, bucket,
        lambda s: _pick_block(s, (256, 128, 64, 32, 16, 8)))
    bn = block_n or tuning.tuned_block(
        "fused_matmul_bias_act", "block_n", n, bucket,
        lambda s: _pick_block(s, (256, 128)))
    bk = block_k or tuning.tuned_block(
        "fused_matmul_bias_act", "block_k", k_dim, bucket,
        lambda s: _pick_block(s, (512, 256, 128)))
    if m % bm or n % bn or k_dim % bk:
        raise ValueError(f"shape ({m},{k_dim})x({k_dim},{n}) not divisible "
                         f"by blocks ({bm},{bk},{bn})")
    grid = (m // bm, n // bn, k_dim // bk)
    has_bias = b is not None
    bias = (b if has_bias else jnp.zeros((n,), jnp.float32)) \
        .astype(jnp.float32)
    kern = functools.partial(_kernel, n_k=grid[2], activation=activation,
                             has_bias=has_bias)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((1, m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, k: (0, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2[None], w[None], bias[None])
    return out[0].reshape(lead + (x.shape[-2], n))


# ---------------------------------------------------------------------------
# differentiable wrapper: Pallas forward, XLA-math backward
# ---------------------------------------------------------------------------


def _act_grad(pre, activation: str):
    """d act(pre) / d pre, from the saved pre-activation (f32)."""
    if activation == "none":
        return jnp.ones_like(pre)
    return jax.grad(lambda p: jnp.sum(apply_fused_activation(p, activation)))(
        pre)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_mm(x, w, b, activation, transpose_a, transpose_b):
    return fused_matmul_bias_act_pallas(
        x, w, b, activation=activation,
        transpose_a=transpose_a, transpose_b=transpose_b)


def _fused_fwd(x, w, b, activation, transpose_a, transpose_b):
    out = _fused_mm(x, w, b, activation, transpose_a, transpose_b)
    return out, (x, w, b)


def _fused_bwd(activation, transpose_a, transpose_b, res, g):
    x, w, b = res
    xa = jnp.swapaxes(x, -1, -2) if transpose_a else x
    wa = jnp.swapaxes(w, -1, -2) if transpose_b else w
    f32 = jnp.float32
    # recompute the pre-activation via plain XLA (no saved (M,N) f32 tensor)
    pre = jnp.matmul(xa, wa, preferred_element_type=f32)
    if b is not None:
        pre = pre + b.astype(f32)
    dpre = (g.astype(f32) * _act_grad(pre, activation))
    dx = jnp.matmul(dpre, jnp.swapaxes(wa, -1, -2),
                    preferred_element_type=f32).astype(x.dtype)
    red = tuple(range(dpre.ndim - 2))
    dw = jnp.sum(jnp.matmul(jnp.swapaxes(xa, -1, -2).astype(dpre.dtype),
                            dpre, preferred_element_type=f32),
                 axis=red).astype(w.dtype)
    if transpose_a:
        dx = jnp.swapaxes(dx, -1, -2)
    if transpose_b:
        dw = jnp.swapaxes(dw, -1, -2)
    db = None if b is None else \
        jnp.sum(dpre, axis=tuple(range(dpre.ndim - 1))).astype(b.dtype)
    return dx, dw, db


_fused_mm.defvjp(_fused_fwd, _fused_bwd)


def fused_matmul_helper(x, w, b=None, *, activation: str = "none",
                        transpose_a: bool = False, transpose_b: bool = False):
    """The registered TPU platform impl: differentiable Pallas forward."""
    return _fused_mm(x, w, b, activation, transpose_a, transpose_b)


def _usable(x, w, b=None, **kw):
    """PlatformHelper::isUsable: documented ranks, Mosaic-aligned tiles,
    no transpose flags (the matcher never emits them aligned; the generic
    handles the rest), a known activation."""
    if kw.get("transpose_a") or kw.get("transpose_b"):
        return False
    if kw.get("activation", "none") not in FUSED_MATMUL_ACTIVATIONS:
        return False
    if getattr(x, "ndim", 0) not in (2, 3) or getattr(w, "ndim", 0) != 2:
        return False
    for a in (x, w):  # integer matmuls stay on the (exact) XLA generic
        dt = getattr(a, "dtype", None)
        # jnp.issubdtype, NOT np: numpy classifies bf16 as non-floating
        if dt is None or not jnp.issubdtype(dt, jnp.floating):
            return False
    if b is not None and getattr(b, "ndim", 0) != 1:
        return False
    m = 1
    for d in x.shape[:-1]:
        m *= d
    k_dim, n = w.shape
    from deeplearning4j_tpu.ops import tuning

    if m < int(tuning.tuned("fused_matmul_bias_act", "pallas_min_m", 8)):
        return False  # measured crossover: tiny row counts stay on XLA
    return m % 8 == 0 and k_dim % 128 == 0 and n % 128 == 0


def _check_fused_matmul_bias_act():
    """Validation case (ops.validation ratchet): generic XLA impl vs a
    numpy oracle, and the Pallas interpret kernel vs both, across the
    activation catalog."""
    import math

    import numpy as np

    r = np.random.RandomState(11)
    x = r.randn(16, 128).astype(np.float32)
    w = r.randn(128, 128).astype(np.float32) * 0.1
    b = r.randn(128).astype(np.float32)

    def oracle(act):
        y = x @ w + b
        if act == "relu":
            return np.maximum(y, 0.0)
        if act == "tanh":
            return np.tanh(y)
        if act == "gelu_exact":
            return y * 0.5 * (1.0 + np.vectorize(math.erf)(y / math.sqrt(2)))
        return y

    for act in ("none", "relu", "tanh", "gelu_exact"):
        want = oracle(act)
        got = fused_matmul_bias_act(jnp.asarray(x), jnp.asarray(w),
                                    jnp.asarray(b), activation=act)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-5)
        got_pl = fused_matmul_bias_act_pallas(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), activation=act)
        np.testing.assert_allclose(np.asarray(got_pl), want,
                                   rtol=1e-4, atol=1e-5)


def register_platform_fused_matmul() -> None:
    """Install the Pallas fused-epilogue kernel as the TPU platform
    override for fused_matmul_bias_act (cuDNN PlatformHelper pattern)."""
    from deeplearning4j_tpu.ops import validation as _validation
    from deeplearning4j_tpu.ops.registry import registry

    reg = registry()
    if "fused_matmul_bias_act" in reg:
        desc = reg.get("fused_matmul_bias_act")
        if "tpu" not in desc.platform_impls:
            reg.register_platform("fused_matmul_bias_act", "tpu",
                                  fused_matmul_helper, _usable)
            _validation.add_case("fused_matmul_bias_act",
                                 _check_fused_matmul_bias_act)
