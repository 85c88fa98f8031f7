"""Pallas TPU prototype: fused BN-apply → 1×1-conv (matmul) → BN-stats.

The ResNet perf analysis (docs/PERF_ANALYSIS.md) shows the training step is
HBM-bound on BatchNorm activation traffic: per conv+BN pair XLA emits three
separate full-activation passes (conv write, stats reduce read, normalize
read+write) because TPU convolutions cannot take fused operands. A 1×1
convolution is a plain matmul over (N·H·W, C) — which Pallas *can* fuse:

    z = relu(x · scale + shift) @ W        # prologue: previous BN's affine
    csum, csq = Σ(z − s), Σ(z − s)²        # epilogue: this BN's shifted stats

reads the raw previous-conv output ONCE and writes z ONCE, eliminating the
standalone normalize pass (read+write) and the stats pass (read) entirely —
a 3×-read/2×-write chain becomes 1×/1×.

Stats use the same running-mean-shifted one-pass moments as
``ops/nn_ops._bn_fwd_math`` (the unshifted E[x²]−E[x]² form is
catastrophic-cancellation-prone; shifting by the running mean keeps it
stable). Per-(m-block, n) partial sums are emitted and tree-reduced by the
caller, so f32 accumulation error stays at the XLA reduce level.

This is the round-5 committed prototype for the "conv+BN epilogue fusion"
lever: `tools/bench_convbn_fusion.py` measures time and XLA cost-analysis
bytes for this kernel vs the unfused XLA chain on real bottleneck shapes.

Reference role: cuDNN's fused ConvScaleBiasActivation / BNStatsFinalize
kernel pairs (platform helpers, SURVEY §3.1); re-designed as a Pallas MXU
matmul with prologue/epilogue fusion rather than a translated kernel.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.registry import current_platform, pallas_interpret


def _pick_block(size: int, candidates=(512, 384, 256, 128)) -> int:
    for c in candidates:
        if size % c == 0:
            return c
    return size


def _kernel(x_ref, sc_ref, sh_ref, w_ref, stat_shift_ref,
            z_ref, csum_ref, csq_ref, acc_ref, *, n_k: int, relu: bool,
            fuse_prologue: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bm, bk) bf16
    if fuse_prologue:
        xf = x.astype(jnp.float32)
        y = xf * sc_ref[0] + sh_ref[0]             # previous BN affine
        if relu:
            y = jnp.maximum(y, 0.0)
        y = y.astype(x.dtype)
    else:
        y = x
    acc_ref[:] += jnp.dot(y, w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        z = acc_ref[:]                             # (bm, bn) f32
        z_ref[0] = z.astype(z_ref.dtype)
        c = z - stat_shift_ref[0]                  # shifted moments
        csum_ref[0, 0] = jnp.sum(c, axis=0)
        csq_ref[0, 0] = jnp.sum(c * c, axis=0)


def fused_bn_matmul_stats(x, scale, shift, w, stat_shift, *, relu: bool = True,
                          fuse_prologue: bool = True, block_m: int = 0,
                          block_n: int = 0, block_k: int = 0,
                          interpret=None):
    """relu(x·scale+shift) @ w with shifted-stats epilogue, one HBM pass.

    x: (M, K) activations (bf16; raw previous-conv output when
    ``fuse_prologue``). scale/shift: (K,) f32 — the previous BN's folded
    affine (γ·inv, β−μ·γ·inv). w: (K, N). stat_shift: (N,) f32 — this BN's
    running mean. Returns (z (M,N), mean (N,), var (N,)) where mean/var are
    this conv's biased batch statistics, ready for the BN running-buffer
    update and normalize scale.
    """
    from deeplearning4j_tpu.ops import tuning

    interpret = pallas_interpret(interpret)
    m, k_dim = x.shape
    n = w.shape[1]
    bucket = tuning.bucket_mkn(m, k_dim, n)
    bm = block_m or tuning.tuned_block("fused_bn_matmul_stats", "block_m",
                                       m, bucket, _pick_block)
    bn = block_n or tuning.tuned_block(
        "fused_bn_matmul_stats", "block_n", n, bucket,
        lambda s: _pick_block(s, (256, 128, 64)))
    bk = block_k or tuning.tuned_block(
        "fused_bn_matmul_stats", "block_k", k_dim, bucket,
        lambda s: _pick_block(s, (512, 256, 128, 64)))
    if m % bm or n % bn or k_dim % bk:
        raise ValueError(f"shape ({m},{k_dim})x({k_dim},{n}) not divisible by "
                         f"blocks ({bm},{bk},{bn})")
    grid = (m // bm, n // bn, k_dim // bk)
    f32 = jnp.float32
    kern = functools.partial(_kernel, n_k=grid[2], relu=relu,
                             fuse_prologue=fuse_prologue)
    z, csum, csq = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((1, m, n), x.dtype),
            jax.ShapeDtypeStruct((grid[0], 1, n), f32),
            jax.ShapeDtypeStruct((grid[0], 1, n), f32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda i, j, k: (0, i, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (i, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (i, 0, j)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), f32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x[None], scale.astype(f32)[None], shift.astype(f32)[None], w[None],
      stat_shift.astype(f32)[None])
    sf = stat_shift.astype(f32)
    m1 = jnp.sum(csum[:, 0], axis=0) / m
    m2 = jnp.sum(csq[:, 0], axis=0) / m
    mean = m1 + sf
    var = jnp.maximum(m2 - jnp.square(m1), 0.0)
    return z[0], mean, var


def _pallas_ok(x, w) -> bool:
    """Use the Pallas kernel only where it wins: TPU backend, bf16
    activations, block-divisible shapes. Everywhere else (CPU mesh, f32
    policy, ragged shapes) the reference XLA chain runs — same math."""
    if os.environ.get("DL4J_TPU_DISABLE_PALLAS_CONVBN") == "1":
        return False
    if current_platform() != "tpu":
        return False
    m, k = x.shape
    n = w.shape[1]
    return (x.dtype == jnp.bfloat16 and m % 128 == 0 and k % 64 == 0
            and n % 64 == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def fused_matmul_bn(x, a, b, w, stat_shift, prologue: bool, relu: bool):
    """Differentiable fused [affine+relu] → matmul → shifted-BN-stats.

    Forward runs the one-HBM-pass Pallas kernel on TPU (reference chain
    elsewhere); backward is the hand-derived two-matmul VJP below — the
    same passes XLA emits for the unfused chain, with no forward recompute.
    ALL THREE outputs (z, mean, var) are differentiable: mean/var feed the
    consumer's normalize affine, so their cotangents carry the batch-stats
    term of standard BN training (reference BatchNormalization backprop).
    ``stat_shift`` (the running mean) only stabilizes the one-pass moments
    and is non-differentiable, exactly like ``_bn_core``.
    """
    z, mean, var = _fused_fwd_dispatch(x, a, b, w, stat_shift, prologue, relu)
    return z, mean, var


def _fused_fwd_dispatch(x, a, b, w, stat_shift, prologue, relu):
    if _pallas_ok(x, w):
        return fused_bn_matmul_stats(x, a, b, w, stat_shift, relu=relu,
                                     fuse_prologue=prologue)
    return reference_bn_matmul_stats(x, a, b, w, stat_shift, relu=relu,
                                     fuse_prologue=prologue)


def _fused_fwd(x, a, b, w, stat_shift, prologue, relu):
    z, mean, var = _fused_fwd_dispatch(x, a, b, w, stat_shift, prologue, relu)
    return (z, mean, var), (x, a, b, w, z, mean)


def _fused_bwd(prologue, relu, res, cts):
    x, a, b, w, z, mean = res
    dz, dmean, dvar = cts
    f32 = jnp.float32
    m = x.shape[0]
    zf = z.astype(f32)
    # fold the stats cotangents into dz: ∂mean/∂z = 1/M,
    # ∂var/∂z = 2(z − mean)/M per column
    dz_eff = dz.astype(f32)
    if dmean is not None:
        dz_eff = dz_eff + dmean / m
    if dvar is not None:
        dz_eff = dz_eff + dvar * (2.0 / m) * (zf - mean)
    if prologue:
        u = x.astype(f32) * a.astype(f32) + b.astype(f32)
        y = jnp.maximum(u, 0.0) if relu else u
        yl = y.astype(x.dtype)
    else:
        yl = x
    dzl = dz_eff.astype(x.dtype)
    dw = jnp.dot(yl.T, dzl, preferred_element_type=f32).astype(w.dtype)
    dy = jnp.dot(dzl, w.T, preferred_element_type=f32)
    if prologue:
        du = jnp.where(u > 0, dy, 0.0) if relu else dy
        da = jnp.sum(du * x.astype(f32), axis=0).astype(a.dtype)
        db = jnp.sum(du, axis=0).astype(b.dtype)
        dx = (du * a.astype(f32)).astype(x.dtype)
    else:
        dx = dy.astype(x.dtype)
        da = jnp.zeros_like(a)
        db = jnp.zeros_like(b)
    # stat_shift is the running mean — non-diff (running buffers are
    # excluded from gradients, reference semantics)
    return dx, da, db, dw, None


fused_matmul_bn.defvjp(_fused_fwd, _fused_bwd)


def reference_bn_matmul_stats(x, scale, shift, w, stat_shift, *,
                              relu: bool = True, fuse_prologue: bool = True,
                              materialize: bool = False):
    """The same math as XLA would run it unfused (the control arm).

    ``materialize=True`` inserts optimization barriers after the affine pass
    and after the matmul — modelling the real full-model behavior, where the
    normalize output and the conv output are HBM-materialized tensors
    (convolutions cannot take fused operands on TPU, and the conv output is
    consumed by more than one downstream pass). Without the barriers XLA
    would fuse this microbenchmark more aggressively than it can fuse the
    actual model, understating the unfused cost.
    """
    f32 = jnp.float32
    if fuse_prologue:
        y = x.astype(f32) * scale.astype(f32) + shift.astype(f32)
        if relu:
            y = jnp.maximum(y, 0.0)
        y = y.astype(x.dtype)
    else:
        y = x
    if materialize:
        y = jax.lax.optimization_barrier(y)
    z = jnp.dot(y, w, preferred_element_type=f32).astype(x.dtype)
    if materialize:
        z = jax.lax.optimization_barrier(z)
    sf = stat_shift.astype(f32)
    c = z.astype(f32) - sf
    m1 = jnp.mean(c, axis=0)
    m2 = jnp.mean(c * c, axis=0)
    mean = m1 + sf
    var = jnp.maximum(m2 - jnp.square(m1), 0.0)
    return z, mean, var
