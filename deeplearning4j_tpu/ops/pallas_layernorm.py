"""Pallas TPU fused LayerNorm(+activation) — `fused_layer_norm`.

XLA computes layer_norm as separate reduce (mean), reduce (var), and
normalize passes; with a downstream GELU the normalized tensor is re-read a
third time. For the transformer block layout (LN → GELU appears in imported
MLP heads and the optimizer's fusion tier routes the chain here —
docs/OPTIMIZER.md § Fusion tier) this kernel makes the one-pass contract
explicit: each (block_rows, D) tile is read from HBM once, mean/variance
reduce on the lane axis in VMEM, the normalize + affine + activation all
apply to the in-register f32 tile, and the finished activation is written
once.

Forward runs Pallas; backward is the custom_vjp XLA path — ``jax.vjp`` of
the generic math (the exact chain XLA already emits fused for the backward;
the fusion win is the forward's eliminated reduce/normalize round-trips),
recomputing from the saved inputs so no (rows, D) f32 residual is stored.
Same design as ``ops/pallas_matmul.py``. Runs in interpret mode off-TPU.

Dispatch: registered as the TPU platform helper for the generic registry
op; the usable() gate requires a Mosaic-aligned trailing dim and at least
the tuning table's measured ``min_rows`` (``ops/tuning.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops.nn_ops import (
    FUSED_MATMUL_ACTIVATIONS, apply_fused_activation)
from deeplearning4j_tpu.ops.registry import op, pallas_interpret


@op("fused_layer_norm")
def fused_layer_norm(x, gain, bias=None, *, axis: int = -1,
                     eps: float = 1e-5, activation: str = "none"):
    """act(layer_norm(x) * gain + bias) — the LN-epilogue fusion target.

    Same contract as the catalog ``layer_norm`` op plus an ``activation``
    epilogue from :data:`FUSED_MATMUL_ACTIVATIONS` (the optimizer's fusion
    tier emits the gelu variants). The generic impl is the exact op chain
    it replaces; the Pallas TPU helper runs it in one HBM pass.

    Trailing-axis only: the (N,)-shaped gain/bias broadcast along the last
    axis, so a non-trailing ``axis`` would normalize one axis and scale
    another — rejected loudly instead of returning silently wrong values
    (the fusion matcher and the graftcheck rule enforce the same)."""
    if axis not in (-1, x.ndim - 1):
        raise ValueError(
            f"fused_layer_norm normalizes the trailing axis only "
            f"(gain/bias are per-last-dim); got axis={axis} for rank "
            f"{x.ndim} — use the catalog layer_norm for other axes")
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps) * gain
    if bias is not None:
        out = out + bias
    return apply_fused_activation(out, activation)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float, activation: str,
            has_bias: bool):
    """One (block_rows, D) tile: mean/var lane reductions in f32, then
    normalize + affine + activation on the in-VMEM tile, one write."""
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    c = x - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    y = c * jax.lax.rsqrt(var + eps) * g_ref[...].astype(jnp.float32)
    if has_bias:
        y = y + b_ref[...].astype(jnp.float32)
    y = apply_fused_activation(y, activation)
    o_ref[...] = y.astype(o_ref.dtype)


def fused_layer_norm_pallas(x, gain, bias=None, *, eps: float = 1e-5,
                            activation: str = "none", block_rows: int = 0,
                            interpret=None):
    """Pallas forward for act(LN(x)·gain+bias) over the trailing axis.

    Leading dims fold into rows; rows must divide by the (tuned) row block
    and D by 128 — the usable() gate guarantees both on the dispatch path."""
    interpret = pallas_interpret(interpret)
    lead = x.shape[:-1]
    d = x.shape[-1]
    rows = 1
    for s in lead:
        rows *= s
    if not block_rows:
        from deeplearning4j_tpu.ops import tuning

        block_rows = tuning.tuned_block(
            "fused_layer_norm", "block_rows", rows,
            tuning.bucket_rows(rows),
            lambda r: next((c for c in (256, 64, 8) if r % c == 0), r))
    if rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by row block "
                         f"{block_rows}")
    x2 = x.reshape(rows, d)
    has_bias = bias is not None
    b = (bias if has_bias else jnp.zeros((d,), x.dtype)).reshape(1, d)
    kern = functools.partial(_kernel, eps=eps, activation=activation,
                             has_bias=has_bias)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, gain.reshape(1, d), b)
    return out.reshape(lead + (d,))


# ---------------------------------------------------------------------------
# differentiable wrapper: Pallas forward, XLA-math backward
# ---------------------------------------------------------------------------


def _generic_f32(x, gain, bias, eps, activation):
    """The reference math at f32 — the backward's recompute target."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    c = xf - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    y = c * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
    y = y + bias.astype(jnp.float32)
    return apply_fused_activation(y, activation)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x, gain, bias, eps, activation):
    return fused_layer_norm_pallas(x, gain, bias, eps=eps,
                                   activation=activation)


def _fused_ln_fwd(x, gain, bias, eps, activation):
    return _fused_ln(x, gain, bias, eps, activation), (x, gain, bias)


def _fused_ln_bwd(eps, activation, res, g):
    x, gain, bias = res
    # jax.vjp of the f32 reference math: the same backward XLA derives for
    # the unfused chain, recomputed from inputs (no saved residuals)
    _, vjp = jax.vjp(
        lambda xx, gg, bb: _generic_f32(xx, gg, bb, eps, activation),
        x, gain, bias)
    dx, dg, db = vjp(g.astype(jnp.float32))
    return (dx.astype(x.dtype), dg.astype(gain.dtype),
            db.astype(bias.dtype))


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm_helper(x, gain, bias=None, *, axis: int = -1,
                            eps: float = 1e-5, activation: str = "none"):
    """The registered TPU platform impl: differentiable Pallas forward."""
    b = bias if bias is not None else jnp.zeros((x.shape[-1],), x.dtype)
    return _fused_ln(x, gain, b, eps, activation)


def _usable(x, gain, bias=None, **kw):
    """PlatformHelper::isUsable: trailing-axis norm only, Mosaic-aligned
    tiles, a known activation, and at least the measured min_rows."""
    ax = kw.get("axis", -1)
    nd = getattr(x, "ndim", 0)
    if nd < 2 or ax not in (-1, nd - 1):
        return False
    if kw.get("activation", "none") not in FUSED_MATMUL_ACTIVATIONS:
        return False
    for a in (x, gain) + (() if bias is None else (bias,)):
        dt = getattr(a, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.floating):
            return False
    if getattr(gain, "ndim", 0) != 1 or gain.shape[0] != x.shape[-1]:
        return False
    if bias is not None and (getattr(bias, "ndim", 0) != 1
                             or bias.shape[0] != x.shape[-1]):
        return False
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    from deeplearning4j_tpu.ops import tuning

    if rows < int(tuning.tuned("fused_layer_norm", "min_rows", 8)):
        return False
    return x.shape[-1] % 128 == 0 and rows % 8 == 0


def _check_fused_layer_norm():
    """Validation case (ops.validation ratchet): generic impl vs a numpy
    oracle, and the Pallas interpret kernel vs both, across activations."""
    import math

    import numpy as np

    r = np.random.RandomState(13)
    x = r.randn(16, 128).astype(np.float32)
    g = (r.rand(128) + 0.5).astype(np.float32)
    b = r.randn(128).astype(np.float32)
    eps = 1e-5

    def oracle(act):
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        y = (x - mean) / np.sqrt(var + eps) * g + b
        if act == "gelu":
            return 0.5 * y * (1.0 + np.tanh(
                math.sqrt(2.0 / math.pi) * (y + 0.044715 * y ** 3)))
        if act == "gelu_exact":
            return y * 0.5 * (1.0 + np.vectorize(math.erf)(y / math.sqrt(2)))
        return y

    for act in ("none", "gelu", "gelu_exact"):
        want = oracle(act)
        got = fused_layer_norm.fn(jnp.asarray(x), jnp.asarray(g),
                                  jnp.asarray(b), eps=eps, activation=act)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-5)
        got_pl = fused_layer_norm_pallas(
            jnp.asarray(x), jnp.asarray(g), jnp.asarray(b), eps=eps,
            activation=act, block_rows=8)
        np.testing.assert_allclose(np.asarray(got_pl), want, rtol=1e-4,
                                   atol=1e-5)


def register_platform_fused_layernorm() -> None:
    """Install the Pallas fused LN(+activation) kernel as the TPU platform
    override for fused_layer_norm (cuDNN PlatformHelper pattern)."""
    from deeplearning4j_tpu.ops import validation as _validation
    from deeplearning4j_tpu.ops.registry import registry

    reg = registry()
    desc = reg.get("fused_layer_norm")
    if "tpu" not in desc.platform_impls:
        reg.register_platform("fused_layer_norm", "tpu",
                              fused_layer_norm_helper, _usable)
        _validation.add_case("fused_layer_norm", _check_fused_layer_norm)
