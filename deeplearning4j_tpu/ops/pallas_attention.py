"""Pallas flash-attention — the cuDNN-platform-helper analog for attention.

Reference parity: libnd4j exposes dot_product_attention as a materialized
O(T²)-memory generic op (SURVEY §6.7 — the reference has NO flash/blockwise
attention). This kernel is the TPU "platform helper" upgrade: blockwise
online-softmax attention that never materializes the (T, T) score matrix,
registered into the op registry's platform table exactly where a cuDNN
helper would override the generic impl (registry.resolve — SURVEY §8.1).
Registration happens at package import (deeplearning4j_tpu.ops), the analog
of libnd4j's OpRegistrator static init.

Kernel design (per pallas_guide.md):
  * grid = (batch*heads, T_q/block_q, T_kv/block_k) with the kv walk as the
    innermost 'arbitrary' dimension: Mosaic streams ONE (block_k, d) k/v
    tile per step, so VMEM stays O(block) no matter how long the sequence
    is (whole-sequence kv refs OOM'd scoped VMEM at T=8192). The
    FlashAttention-2 running state (acc, row max m, denom l) lives in VMEM
    scratch across the kv iterations of a q block; both matmuls per step
    hit the MXU in the operands' NATIVE dtype with f32 accumulation (an
    up-front f32 cast forces Mosaic's multi-pass f32 path — measured ~8×
    slower for bf16 inputs). The forward also emits log-sum-exp rows.
  * backward is Pallas too (FlashAttention-2 backward): a dq kernel and a
    dk/dv kernel with the same streaming-grid shape, recomputing
    p = exp(s - lse) blockwise so the (T, T) score matrix never exists in
    HBM in either direction.
  * layouts avoid lane-1 tensors: the key mask rides (BH, n_blocks, 8,
    block_k) full-trailing-dim blocks (kv positions on the lane axis) and
    lse/delta ride (…, 8) broadcast buffers. Lane-1 ((T, 1)) masks/rows
    force padded tiles and in-kernel transposes — measured 9× end-to-end
    slowdown and spurious scoped-VMEM OOMs at wide blocks.
  * attention-prob dropout runs INSIDE the kernel (counter-based hash on
    absolute (head, row, col) positions → threshold-on-uniform), so the
    backward kernels regenerate the identical keep mask from the same seed
    instead of materializing a (T, T) mask in HBM. The softmax denominator
    is accumulated un-dropped (dropout applies after normalization,
    matching the reference's post-softmax dropout semantics).
  * block sizes default to 512 (capped to T): fewer, fatter grid steps
    amortize per-step overhead. No speed-up over the XLA generic is claimed
    here: `flash_attn_roofline.train` (PERF.md) is what the chip measured.

Runs in interpret mode off-TPU so CPU tests exercise the same code path.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# pltpu ships with jax's pallas package and is needed even in interpret mode
# (VMEM scratch allocations); a build without it cannot run these kernels.
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.registry import pallas_interpret

logger = logging.getLogger(__name__)

# Shortest kv length at which the Pallas kernel is taken over the XLA fused /
# generic materialized paths. The only evidence for 4096 was the 2026-07
# rig's attention sweep (flash at 0.65-0.99x of XLA below it), and that
# machine's numbers are void: the v5e has not re-measured it (ROADMAP.md
# D15). A measured per-device value lives in the tuning table
# (ops/tuning.py, refreshed by tools/tune.py or
# tools/bench_attention_sweep.py) and DL4J_TPU_FLASH_MIN_T still wins.
FLASH_MIN_T_DEFAULT = 4096

# parse-once cache: (raw env string, device kind, resolved threshold).
# Re-parsing (and re-warning) on every resolve call was the round-9 bugfix
# target; the raw string keys the cache so env re-pointing and
# monkeypatching stay live, and the device kind keys it because the tuned
# fallback is per-device — a CPU-scoped resolve (the consistency suite
# runs under jax.default_device(cpu)) must not pin the CPU table's
# threshold for subsequent TPU resolves.
_FLASH_MIN_T_CACHE: "Optional[tuple]" = None


def reset_flash_min_t_cache() -> None:
    """Test seam + tuning-table invalidation hook."""
    global _FLASH_MIN_T_CACHE
    _FLASH_MIN_T_CACHE = None


def _tuned_flash_min_t() -> int:
    from deeplearning4j_tpu.ops import tuning

    return int(tuning.tuned("dot_product_attention", "flash_min_t",
                            FLASH_MIN_T_DEFAULT))


def flash_min_t() -> int:
    """Live dispatch threshold: kv lengths below this use the XLA path.

    Resolution order: ``DL4J_TPU_FLASH_MIN_T`` env override, then the
    measured tuning table for the target device kind, then the checked-in
    default. The parsed value is cached against the raw env string, so a
    serving process can still be re-pointed without code changes but the
    parse (and the invalid-value warning) happen once per distinct value,
    not once per resolve call."""
    import os

    from deeplearning4j_tpu.ops import tuning

    global _FLASH_MIN_T_CACHE
    raw = os.environ.get("DL4J_TPU_FLASH_MIN_T")
    # kind participates even with the env set: the invalid-raw fallback is
    # the tuned (per-device) value too. jax memoizes the devices() probe.
    kind = tuning.current_device_kind()
    if _FLASH_MIN_T_CACHE is not None and _FLASH_MIN_T_CACHE[:2] == (raw,
                                                                    kind):
        return _FLASH_MIN_T_CACHE[2]
    if raw:
        try:
            val = int(raw)
        except ValueError:
            val = _tuned_flash_min_t()
            logger.warning(
                "invalid DL4J_TPU_FLASH_MIN_T=%r — falling back to the "
                "tuned/default threshold %d", raw, val)
    else:
        val = _tuned_flash_min_t()
    _FLASH_MIN_T_CACHE = (raw, kind, val)
    return val


def _keep_mask(seed, bh, q0, k0, *, block_q: int, block_k: int, rate: float):
    """Deterministic per-element keep mask for one (block_q, block_k) tile.

    Counter-based: a murmur-style integer mix of (seed, batch·head, absolute
    row, absolute col) thresholded against the rate. Both backward kernels
    call this with the same absolute coordinates, regenerating the exact
    forward mask — the FlashAttention dropout recipe, with a stateless hash
    instead of saved RNG state so it runs identically under Mosaic and
    interpret mode."""
    rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    h = seed + bh * jnp.int32(7919) \
        + rows * jnp.int32(1103515245) + cols * jnp.int32(1299709)
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * jnp.int32(1274126177)
    h = h ^ jax.lax.shift_right_logical(h, 16)
    u = (h & jnp.int32(0xFFFFFF)).astype(jnp.float32) * (1.0 / (1 << 24))
    return u >= rate


def _mm(a, b, dims):
    """MXU matmul with f32 accumulation in the operands' NATIVE dtype.

    Casting operands up to f32 before the dot forces Mosaic's multi-pass
    f32 MXU path (~8× slower); bf16 inputs should hit the native bf16 MXU
    with an f32 accumulator. Mixed-dtype pairs cast the wider operand DOWN
    to the narrower one — the FlashAttention convention for p @ v (the f32
    softmax probs drop to the input dtype for the second matmul)."""
    if a.dtype != b.dtype:
        narrow = a.dtype if a.dtype.itemsize <= b.dtype.itemsize else b.dtype
        a, b = a.astype(narrow), b.astype(narrow)
    # precision pinned explicitly: an ambient default_matmul_precision
    # context (the f32 dtype policy sets 'high') must not leak into the
    # kernel — Mosaic only lowers DEFAULT/HIGHEST, and operand dtype plus
    # the f32 accumulator already define this kernel's numerics
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)


def _mm_nt(a, b):  # a @ b.T
    return _mm(a, b, ((1,), (1,)))


def _mm_nn(a, b):  # a @ b
    return _mm(a, b, ((1,), (0,)))


def _mm_tn(a, b):  # a.T @ b
    return _mm(a, b, ((0,), (0,)))


def _mask_scores(s, qi, ki_start, mblk, *, block_q: int, block_k: int,
                 causal: bool):
    """Apply the kv mask row and the causal mask to one (block_q, block_k)
    tile. mblk: (1, block_k) 0/1 — covers both user key-padding and kv
    zero-padding."""
    s = jnp.where(mblk > 0.5, s, -1e30)
    if causal:
        k_pos = ki_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = jnp.where(q_pos >= k_pos, s, -1e30)
    return s


def _attn_kernel(q_ref, k_ref, v_ref, m_ref, seed_ref, o_ref, lse_ref,
                 acc_ref, mx_ref, l_ref, *, block_k: int, scale: float,
                 causal: bool, block_q: int, dropout_rate: float):
    """One (q-block, kv-block) grid step. The kv walk is the innermost
    ('arbitrary') grid dimension so Mosaic streams one (block_k, d) k/v tile
    per step — VMEM stays O(block) regardless of T (whole-sequence kv refs
    blew the 16 MB scoped-VMEM budget at T=8192). The FlashAttention-2
    running state (acc, row max, denom) lives in VMEM scratch across the kv
    iterations of one q block."""
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        mx_ref[:] = jnp.full_like(mx_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        vblk = v_ref[0]
        mblk = m_ref[0, 0, :1]  # (1, block_k)
        s = _mm_nt(q_ref[0], k_ref[0]) * scale  # f32 (block_q, block_k)
        s = _mask_scores(s, qi, ki * block_k, mblk, block_q=block_q,
                         block_k=block_k, causal=causal)
        m_prev = mx_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        # denominator accumulates UN-dropped p: softmax normalizes first,
        # dropout hits the normalized probs (reference post-softmax order)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0, 0], bh, qi * block_q, ki * block_k,
                              block_q=block_q, block_k=block_k,
                              rate=dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        acc_ref[:] = acc_ref[:] * alpha + _mm_nn(p, vblk)
        mx_ref[:, :1] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse rides a 128-lane buffer (value broadcast) to dodge lane-1 tiles
        lse_ref[0] = jnp.broadcast_to(mx_ref[:, :1] + jnp.log(l),
                                      lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, m_ref, seed_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_ref, *, block_k: int, scale: float,
               causal: bool, block_q: int, dropout_rate: float):
    """dq_i = scale * Σ_j p_ij (dO_i·v_j·keep/(1-r) - Δ_i) k_j, p from lse.
    Grid (bh, q blocks, kv blocks): kv streams innermost, dq accumulates in
    VMEM scratch."""
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        lse = lse_ref[0][:, :1]  # (block_q, 1) row of the 128-lane buffer
        delta = delta_ref[0][:, :1]
        kblk = k_ref[0]
        mblk = m_ref[0, 0, :1]  # (1, block_k)
        s = _mm_nt(q_ref[0], kblk) * scale
        s = _mask_scores(s, qi, ki * block_k, mblk, block_q=block_q,
                         block_k=block_k, causal=causal)
        p = jnp.exp(s - lse)
        dp = _mm_nt(do_ref[0], v_ref[0])
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0, 0], bh, qi * block_q, ki * block_k,
                              block_q=block_q, block_k=block_k,
                              rate=dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta)
        acc_ref[:] = acc_ref[:] + _mm_nn(ds, kblk)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, m_ref, seed_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                scale: float, causal: bool, block_k: int,
                dropout_rate: float):
    """dk_j = Σ_i ds_ij (scale·q_i); dv_j = Σ_i p̃_ij dO_i. Grid (bh, kv
    blocks, q blocks): q streams innermost, dk/dv accumulate in VMEM scratch
    (zero-padded q rows contribute nothing since their dO rows are zero).
    p̃ is the dropped/rescaled prob when dropout is on."""
    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        kblk = k_ref[0]  # (block_k, d)
        mblk = m_ref[0, 0, :1]  # (1, block_k)
        qblk = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # (block_q, 1) row of the 128-lane buffer
        delta = delta_ref[0][:, :1]
        s = _mm_nt(qblk, kblk) * scale  # (block_q, block_k)
        s = _mask_scores(s, qi, ki * block_k, mblk, block_q=block_q,
                         block_k=block_k, causal=causal)
        p = jnp.exp(s - lse)
        dp = _mm_nt(do, v_ref[0])
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0, 0], bh, qi * block_q, ki * block_k,
                              block_q=block_q, block_k=block_k,
                              rate=dropout_rate)
            p_drop = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        else:
            p_drop = p
        ds = p * (dp - delta) * scale  # fold dk's scale factor in here
        dk_acc[:] = dk_acc[:] + _mm_tn(ds, qblk)
        dv_acc[:] = dv_acc[:] + _mm_tn(p_drop, do)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_to_blocks(q, k, v, kv_mask, block_q, block_k):
    """Pad sequence dims to block multiples; fold kv padding and the user
    key mask into one (BH, T_kv_padded) 0/1 f32 tensor; builders reshape it
    to (BH, n_kv_blocks, 8, block_k) (8 broadcast sublanes — Mosaic requires
    the last two block dims divisible by (8, 128) or full) so each grid step
    gets its mask row as a FULL trailing-dim block — the kv positions stay on the lane
    axis (a lane-1 (T_kv, 1) layout forces padded tiles and in-kernel
    transposes; measured 9× slower end-to-end) and no Mosaic lane-alignment
    constraint applies at any block size."""
    bh, t_q, d = q.shape
    t_kv = k.shape[1]

    def clamp(block, t):
        # cap to the (rounded-up) seq len, then round up to a multiple of 8
        # — Pallas requires sublane-dim blocks divisible by 8
        return -(-min(block, max(t, 8)) // 8) * 8

    block_q = clamp(block_q, t_q)
    block_k = clamp(block_k, t_kv)
    pad_q = (-t_q) % block_q
    pad_k = (-t_kv) % block_k
    if kv_mask is None:
        m = jnp.ones((bh, t_kv), jnp.float32)
    else:
        m = jnp.broadcast_to(kv_mask.reshape(bh, t_kv).astype(jnp.float32),
                             (bh, t_kv))
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
        m = jnp.pad(m, ((0, 0), (0, pad_k)))  # padded keys masked out
    return q, k, v, m, block_q, block_k, pad_q, pad_k


def _default_blocks(block_q, block_k, t_kv: Optional[int] = None):
    """Default tile size 512 (capped to T by _pad_to_blocks): fewer, fatter
    grid steps amortize per-step overhead — measured 14.8 ms vs 26 ms
    (block 128) for a T=8192 d=64 forward on a v5e. The lane-1 mask/lse
    layouts were what made wide blocks OOM scoped VMEM before; with 128-lane
    buffers every probed shape (T=512…8192, fwd+bwd) compiles at 512.

    When the caller passed no explicit block, the measured tuning table
    (ops/tuning.py, keyed on device kind + kv-length bucket) overrides the
    512 fallback — the autotuner's winners feed real dispatch."""
    if block_q is None or block_k is None:
        from deeplearning4j_tpu.ops import tuning

        bucket = tuning.bucket_t(t_kv) if t_kv else None
        if block_q is None:
            block_q = int(tuning.tuned("dot_product_attention", "block_q",
                                       512, bucket=bucket))
        if block_k is None:
            block_k = int(tuning.tuned("dot_product_attention", "block_k",
                                       512, bucket=bucket))
    return block_q, block_k


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_fwd(q, k, v, kv_mask, seed, *, scale: float, causal: bool,
               block_q: int, block_k: int, interpret: bool,
               dropout_rate: float):
    bh, t_q, d = q.shape
    q, k, v, m, block_q, block_k, pad_q, _ = _pad_to_blocks(
        q, k, v, kv_mask, block_q, block_k)
    tkv_p = k.shape[1]
    m = jnp.broadcast_to(m.reshape(bh, tkv_p // block_k, 1, block_k),
                         (bh, tkv_p // block_k, 8, block_k))
    grid = (bh, (t_q + pad_q) // block_q, tkv_p // block_k)
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, scale=scale, causal=causal,
        block_q=block_q, dropout_rate=dropout_rate)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q + pad_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q + pad_q, 8), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, 8, block_k), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, m, seed)
    return out[:, :t_q], lse[:, :t_q]


def _flash_bwd(q, k, v, kv_mask, seed, out, lse, g, *, scale: float,
               causal: bool, block_q: int, block_k: int, interpret: bool,
               dropout_rate: float):
    bh, t_q, d = q.shape
    t_kv = k.shape[1]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, t_q, 1)
    delta = jnp.broadcast_to(delta, (bh, t_q, 8))  # 8-lane buffer
    q, k, v, m, block_q, block_k, pad_q, pad_k = _pad_to_blocks(
        q, k, v, kv_mask, block_q, block_k)
    if pad_q:
        g = jnp.pad(g, ((0, 0), (0, pad_q), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pad_q), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q), (0, 0)))
    tq_p, tkv_p = t_q + pad_q, t_kv + pad_k
    m = jnp.broadcast_to(m.reshape(bh, tkv_p // block_k, 1, block_k),
                         (bh, tkv_p // block_k, 8, block_k))

    dq_kernel = functools.partial(
        _dq_kernel, block_k=block_k, scale=scale, causal=causal,
        block_q=block_q, dropout_rate=dropout_rate)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d), q.dtype),
        grid=(bh, tq_p // block_q, tkv_p // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, 8, block_k), lambda b, i, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, m, seed, g, lse, delta)

    dkv_kernel = functools.partial(
        _dkv_kernel, block_q=block_q, scale=scale, causal=causal,
        block_k=block_k, dropout_rate=dropout_rate)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, tkv_p, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tkv_p, d), v.dtype),
        ],
        grid=(bh, tkv_p // block_k, tq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, 1, 8, block_k), lambda b, j, i: (b, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, j, i: (0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, m, seed, g, lse, delta)
    return dq[:, :t_q], dk[:, :t_kv], dv[:, :t_kv]


def _reference_attention(q, k, v, *, scale: float, causal: bool, kv_mask=None):
    """The generic O(T²) path (libnd4j dot_product_attention math) — used
    as oracle and platform fallback."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if kv_mask is not None:
        s = jnp.where(kv_mask.reshape(q.shape[0], 1, k.shape[1]) > 0.5, s, -1e30)
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, kv_mask=None, dropout_seed=None,
                    scale: Optional[float] = None, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    dropout_rate: float = 0.0):
    """Blockwise attention over (BH, T, D) tensors (fold batch×heads first).

    ``kv_mask``: optional (BH, T_kv) 0/1 key-padding mask (1 = attend).
    ``dropout_rate``/``dropout_seed``: post-softmax attention-prob dropout
    applied inside the kernels (seed: any int32 array; None with rate>0 is an
    error). block_q/block_k=None picks VMEM-safe defaults. Forward AND
    backward run Pallas kernels (FlashAttention-2 recurrences); neither the
    (T, T) score matrix nor the dropout mask ever reaches HBM."""
    return _flash_call(q, k, v, kv_mask, dropout_seed, scale, causal,
                       block_q, block_k, interpret, dropout_rate)[0]


def _norm_seed(dropout_seed, dropout_rate):
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("flash attention dropout_rate > 0 needs dropout_seed")
    if dropout_seed is None:
        return jnp.zeros((1, 1), jnp.int32)
    return jnp.asarray(dropout_seed).reshape(-1)[:1].astype(jnp.int32) \
              .reshape(1, 1)


def _flash_call(q, k, v, kv_mask, dropout_seed, scale, causal, block_q,
                block_k, interpret, dropout_rate):
    if causal and q.shape[1] != k.shape[1]:
        # the kernel's causal mask is start-aligned on raw positions; the
        # reference path is end-aligned — they only agree for t_q == t_kv,
        # so reject the ambiguous case instead of silently training against
        # a different attention pattern
        raise ValueError(
            f"causal flash attention requires t_q == t_kv, got "
            f"{q.shape[1]} vs {k.shape[1]}")
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k = _default_blocks(block_q, block_k, k.shape[1])
    seed = _norm_seed(dropout_seed, dropout_rate)
    return _flash_fwd(q, k, v, kv_mask, seed, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k,
                      interpret=pallas_interpret(interpret),
                      dropout_rate=dropout_rate)


def _fwd(q, k, v, kv_mask, dropout_seed, scale, causal, block_q, block_k,
         interpret, dropout_rate):
    out, lse = _flash_call(q, k, v, kv_mask, dropout_seed, scale, causal,
                           block_q, block_k, interpret, dropout_rate)
    return out, (q, k, v, kv_mask, dropout_seed, out, lse)


def _bwd(scale, causal, block_q, block_k, interpret, dropout_rate, res, g):
    q, k, v, kv_mask, dropout_seed, out, lse = res
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k = _default_blocks(block_q, block_k, k.shape[1])
    seed = _norm_seed(dropout_seed, dropout_rate)
    dq, dk, dv = _flash_bwd(q, k, v, kv_mask, seed, out, lse, g, scale=s,
                            causal=causal, block_q=block_q, block_k=block_k,
                            interpret=pallas_interpret(interpret),
                            dropout_rate=dropout_rate)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fwd, _bwd)


def rng_to_seed(rng):
    """Fold a JAX PRNG key (typed or raw uint32) into a (1,1) int32 kernel
    seed. None passes through."""
    if rng is None:
        return None
    try:
        data = jax.random.key_data(rng)
    except Exception:
        data = jnp.asarray(rng)
    return data.reshape(-1)[-1:].astype(jnp.int32).reshape(1, 1)


def flash_mha(q, k, v, *, num_heads: int, causal: bool = False,
              kv_mask=None, interpret: Optional[bool] = None,
              dropout_rate: float = 0.0, dropout_rng=None):
    """(N, T, H*dh) convenience wrapper: split heads, run flash, re-merge.
    ``kv_mask``: optional (N, T_kv) key-padding mask; ``dropout_rng``: a JAX
    PRNG key enabling in-kernel attention-prob dropout."""
    n, t, d = q.shape
    dh = d // num_heads

    def split(a):
        return a.reshape(n, a.shape[1], num_heads, dh).transpose(0, 2, 1, 3) \
                .reshape(n * num_heads, a.shape[1], dh)

    m = None
    if kv_mask is not None:
        m = jnp.repeat(kv_mask.astype(jnp.float32), num_heads, axis=0)
    out = flash_attention(split(q), split(k), split(v), m,
                          rng_to_seed(dropout_rng), None, causal,
                          None, None, interpret, dropout_rate)
    return out.reshape(n, num_heads, t, dh).transpose(0, 2, 1, 3).reshape(n, t, d)


# ---------------------------------------------------------------------------
# Paged decode attention — the serving-side kernel (docs/SERVING.md)
# ---------------------------------------------------------------------------
#
# Generation serves ONE query token per sequence against the block-paged KV
# pool of serving/cache.py (the vLLM/PagedAttention memory model). There is
# ONE layout: ``kv_pages`` is ``(layers, 2, num_pages + 1, page_size,
# heads * head_dim)`` with heads and head size MERGED in the minor dimension,
# so a page is a ``(page_size, heads * head_dim)`` matrix that fills the
# TPU's (8, 128) float32 / (16, 128) bfloat16 tiles with no padding, the
# device keeps the pool row-major, and every program updates it in place.
# The op takes the WHOLE pool and a static ``layer``: slicing a layer out
# first would copy it.
#
# Two implementations of one contract, selected through the registry
# platform table exactly like flash attention above:
#   * `paged_decode_attention_xla` — generic: gather the page table's pages
#     out of the pool and run masked attention; runs anywhere, and is what
#     narrow models (heads * head_dim not a multiple of 128) take on a TPU
#     too, at the cost of materializing the gathered (S, T_max, H, D) keys.
#   * `_paged_decode_call` — Pallas: grid (slot, group of pages) with the page
#     walk innermost; the page table rides scalar-prefetch
#     (PrefetchScalarGridSpec) and the layer is a constant of the index_map,
#     so each grid step DMAs its pages' K and V straight from where they lie
#     in the pool — neither a gathered copy nor a layer's slice ever exists —
#     and the walk stops at the sequence's length. Online-softmax running
#     state lives in VMEM scratch across the page walk of one slot (the
#     FlashAttention-2 recurrence, a group of pages at a time).


def gather_pages(kv_pages, layer: int, side: int, pages):
    """``kv_pages[layer, side, pages]``: the pages ``pages`` (any shape of
    int32) of one layer's K (``side`` 0) or V (1) — or of a latent pool's one
    side —, ``pages.shape + (page, width)``, as a gather with ONE index a
    page over the pool seen as a run of pages (a bitcast of the row-major
    pool, no copy). Written ``kv_pages[layer, side, pages]`` the TPU compiler
    packs the three index components into bit fields of one word, and the
    program that unpacks them halted the v5e at the serving cell's pool size
    (PERF.md, PR 26)."""
    n_l, n_s, n_p, page, width = kv_pages.shape
    run = kv_pages.reshape(n_l * n_s * n_p, page, width)
    return run[(layer * n_s + side) * n_p + pages]


def paged_decode_attention_xla(q, kv_pages, page_table, seq_lens, *,
                               layer: int = 0,
                               scale: Optional[float] = None):
    """Generic gather path: q:[S,H,D], kv_pages:[L,2,P,page,H*D] (the whole
    pool), page_table:[S,max_pages] int32, seq_lens:[S] int32 -> [S,H,D];
    ``layer`` picks the pool's layer.

    Scores accumulate in f32 regardless of cache dtype (matches the Pallas
    kernel's preferred_element_type accumulators)."""
    s_n, h, d = q.shape
    page = kv_pages.shape[3]
    max_pages = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k, v = (gather_pages(kv_pages, layer, side, page_table).reshape(
        s_n, max_pages * page, h, d) for side in (0, 1))
    s = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("sht,sthd->shd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# Pages one grid step of the paged kernel attends to. A grid step has a cost
# whatever it does, and one page of 16 positions gives the MXU a sixteenth of
# a tile to do; 8 pages of 16 make the scores one full 128-lane tile. On the
# v5e at GPT-2 small's widths (32 slots x 65 pages of 16) the whole decode
# program took 11.8, 9.4, 8.3, 7.8 and 8.0 ms at 1, 2, 4, 8 and 16 pages a
# step (PERF.md, PR 26).
_PAGES_PER_STEP = 8


def _paged_decode_kernel(pt_ref, sl_ref, q_ref, *refs, page: int,
                         scale: float, head_dim: int, group: int):
    """One (slot, group of pages) grid step over merged-head pages.

    Row ``h`` of the block-diagonal query holds head ``h``'s query in its
    own ``head_dim`` columns and zeros elsewhere, so ONE (rows, H*D) x
    (H*D, positions) matmul gives every head's scores and ONE (rows,
    positions) x (positions, H*D) matmul every head's values; the diagonal
    blocks of the accumulator are the output. The zeros cost operations the
    MXU has to spare (decode is bound by the K/V stream) and save the
    per-head lane slices of an unaligned ``head_dim``.

    A group wholly past ``seq_len`` is skipped (its probabilities would all
    be exactly 0), and the index maps re-point its pages at the sequence's
    last page, which the pipeline then does not fetch again: the walk over
    the page table costs DMA and arithmetic only as far as the sequence
    goes. Within the last group, positions past ``seq_len`` are masked."""
    kv_refs, (o_ref, acc_ref, mx_ref, l_ref) = refs[:group], refs[group:]
    s_idx, j = pl.program_id(0), pl.program_id(1)
    rows, width = acc_ref.shape      # heads rounded up to 8, heads * head_dim
    span = group * page
    seq_len = sl_ref[s_idx]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        mx_ref[:] = jnp.full_like(mx_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    first = head_dim * jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    own = (col >= first) & (col < first + head_dim)

    @pl.when(j * span < seq_len)
    def _attend():
        # selected in f32: a bf16 select would need the int32 mask in bf16's
        # (16, 128) tiling, a relayout Mosaic refuses
        q = jnp.where(own, q_ref[:].astype(jnp.float32), 0.0).astype(
            q_ref.dtype)             # (rows, H*D)
        kblk = jnp.concatenate([r[0] for r in kv_refs], axis=0)
        vblk = jnp.concatenate([r[1] for r in kv_refs], axis=0)

        s = _mm_nt(q, kblk) * scale  # f32 (rows, span)
        pos = j * span + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        s = jnp.where(pos < seq_len, s, -1e30)

        m_prev = mx_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _mm_nn(p, vblk)
        mx_ref[:, :1] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out = jnp.where(own, acc_ref[:] / l, 0.0)
        o_ref[:] = out.sum(axis=0, keepdims=True).astype(o_ref.dtype)


def _paged_decode_call(q, kv_pages, page_table, seq_lens, *, layer: int = 0,
                       scale: Optional[float] = None,
                       interpret: Optional[bool] = None):
    """Pallas paged decode. Same contract as paged_decode_attention_xla,
    except that a slot of length 0 (inactive: nobody reads it) gives zeros
    where the gather path gives the mean of its masked values."""
    s_n, h, d = q.shape
    page, width = kv_pages.shape[3], kv_pages.shape[4]
    max_pages = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    interpret = pallas_interpret(interpret)
    rows = -(-h // 8) * 8
    group = min(_PAGES_PER_STEP, max_pages)
    kernel = functools.partial(_paged_decode_kernel, page=page, scale=scale,
                               head_dim=d, group=group)
    layer = int(layer)

    def page_block(i):
        """K and V of the group's ``i``-th page of one layer, where they
        lie in the pool; past the sequence's end, its last page again."""
        def index(s, j, pt, sl):
            last = jnp.maximum(sl[s] - 1, 0) // page
            return (layer, 0, pt[s, jnp.minimum(j * group + i, last)], 0, 0)

        return pl.BlockSpec((None, 2, None, page, width), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_n, -(-max_pages // group)),
        in_specs=[pl.BlockSpec((None, 1, width),
                               lambda s, j, pt, sl: (s, 0, 0))]
        + [page_block(i) for i in range(group)],
        out_specs=pl.BlockSpec((None, 1, width),
                               lambda s, j, pt, sl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, width), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, 1, width), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q.reshape(s_n, 1, width), *([kv_pages] * group))
    return out.reshape(s_n, h, d)


def _paged_usable(q, kv_pages, page_table, seq_lens, **kw):
    """PlatformHelper::isUsable for the Pallas paged path: the documented
    ranks, a page that fills whole tiles as it crosses the DMA (merged width
    a multiple of 128 lanes, page size of 8 sublanes), and a page walk long
    enough to beat the XLA gather (measured min_pages from the tuning table;
    default 1 = always, matching pre-tuning behavior). Narrow models (the
    tiny test configurations) take the generic path on every platform."""
    if getattr(q, "ndim", 0) != 3 or getattr(kv_pages, "ndim", 0) != 5:
        return False
    if getattr(page_table, "ndim", 0) != 2 or getattr(seq_lens, "ndim", 0) != 1:
        return False
    if kv_pages.shape[1] != 2 or \
            q.shape[1] * q.shape[2] != kv_pages.shape[4]:
        return False
    from deeplearning4j_tpu.ops import tuning

    if page_table.shape[1] < int(tuning.tuned("paged_decode_attention",
                                              "min_pages", 1)):
        return False
    return kv_pages.shape[4] % 128 == 0 and kv_pages.shape[3] % 8 == 0


def _check_paged_decode_attention():
    """Validation case (ops.validation ratchet): XLA gather path vs a
    straight numpy oracle, and the Pallas interpret kernel vs both, on the
    second layer of a two-layer pool."""
    import numpy as np

    r = np.random.RandomState(7)
    s_n, h, d, page, n_pages, max_pages, layer = 3, 4, 32, 8, 10, 3, 1
    q = r.randn(s_n, h, d).astype(np.float32)
    kv = r.randn(2, 2, n_pages, page, h * d).astype(np.float32)
    pt = np.stack([r.choice(n_pages, max_pages, replace=False)
                   for _ in range(s_n)]).astype(np.int32)
    sl = np.array([5, 17, 24], np.int32)
    scale = 1.0 / math.sqrt(d)
    want = np.zeros_like(q)
    for i in range(s_n):
        gk = kv[layer, 0, pt[i]].reshape(-1, h, d)[:sl[i]]
        gv = kv[layer, 1, pt[i]].reshape(-1, h, d)[:sl[i]]
        for hh in range(h):
            sc = gk[:, hh] @ q[i, hh] * scale
            p = np.exp(sc - sc.max())
            p = p / p.sum()
            want[i, hh] = p @ gv[:, hh]
    args = (jnp.asarray(q), jnp.asarray(kv), jnp.asarray(pt), jnp.asarray(sl))
    got = paged_decode_attention_xla(*args, layer=layer)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    assert _paged_usable(*args)
    got_pl = _paged_decode_call(*args, layer=layer)
    np.testing.assert_allclose(np.asarray(got_pl), want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Latent decode attention (MLA, projections absorbed) over a latent paged pool
# ---------------------------------------------------------------------------
#
# A latent cache holds ONE row a token an attention sub-layer: the normalised
# latent ``c`` (``value_width`` values, which are the keys' content part AND
# the values) followed by the rotated positional key shared by every head,
# then dead lanes up to a multiple of 128 so that the device keeps the pool
# row-major and unpadded: ``(sub-layers, 1, P, page, W)``. With the key and
# value up-projections absorbed into the query and the output, decode is all
# the heads against that one row: ``score_h = (q_abs_h . c + q_rope_h . k_r)
# * scale`` and ``o_h = sum_t p_ht c_t``. Two implementations of one
# contract, as above: the generic gather (every platform) and a Pallas kernel
# that reads the pages where they lie.


# Pages a grid step: a latent page is 20 KB at the published widths (16 rows
# of 640 bfloat16), a fifth of a GPT-2 page's K and V, and a grid step's cost
# is the wait for its pages more than their bytes. Timed on the v5e at the
# serving cell's geometry (128 slots, contexts ~550, 96 pages a sequence),
# the call by pages a step 4, 8, 16, 32, 48, 96: 1.53, 1.19, 1.07, 0.99,
# 1.00, 1.00 ms (PERF.md, PR 27).
_LATENT_PAGES_PER_STEP = 32


def _latent_query(q_abs, q_rope, width: int):
    """[q_abs | q_rope | 0]: one query row a head over the pool's row."""
    s_n, h, r = q_abs.shape
    dead = width - r - q_rope.shape[-1]
    return jnp.concatenate(
        [q_abs, q_rope, jnp.zeros((s_n, h, dead), q_abs.dtype)], axis=-1)


def latent_decode_attention_xla(q_abs, q_rope, kv_pages, page_table, seq_lens,
                                *, layer: int = 0, scale: float,
                                value_width: Optional[int] = None):
    """Generic gather path: q_abs:[S,H,R], q_rope:[S,H,Dr], kv_pages:
    [L,1,P,page,W] (the whole pool, W >= R + Dr), page_table:[S,max_pages],
    seq_lens:[S] -> [S,H,R] (the probabilities over the latents; the caller
    projects them through the values' up-projection). Scores and softmax in
    float32."""
    s_n, h, r = q_abs.shape
    value_width = r if value_width is None else value_width
    page, width = kv_pages.shape[3], kv_pages.shape[4]
    max_pages = page_table.shape[1]
    with jax.named_scope("latent_decode_attention"):
        rows = gather_pages(kv_pages, layer, 0, page_table).reshape(
            s_n, max_pages * page, width).astype(jnp.float32)
        q = _latent_query(q_abs, q_rope, width).astype(jnp.float32)
        s = jnp.einsum("shw,stw->sht", q, rows) * scale
        pos = jnp.arange(max_pages * page)
        s = jnp.where(pos[None, None, :] < seq_lens[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("sht,stv->shv", p,
                          rows[..., :value_width]).astype(q_abs.dtype)


def _latent_decode_kernel(pt_ref, sl_ref, q_ref, *refs, page: int,
                          scale: float, group: int, value_width: int):
    """One (slot, group of pages) grid step: every head's scores against the
    group's rows in ONE (H, W) x (W, positions) product and every head's
    weighted latents in ONE (H, positions) x (positions, R) product. The
    walk stops at the sequence's length as in ``_paged_decode_kernel``."""
    kv_refs, (o_ref, acc_ref, mx_ref, l_ref) = refs[:group], refs[group:]
    s_idx, j = pl.program_id(0), pl.program_id(1)
    rows = acc_ref.shape[0]
    span = group * page
    seq_len = sl_ref[s_idx]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        mx_ref[:] = jnp.full_like(mx_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j * span < seq_len)
    def _attend():
        blk = jnp.concatenate([r[:] for r in kv_refs], axis=0)  # (span, W)
        s = _mm_nt(q_ref[:], blk) * scale                       # f32 (H, span)
        pos = j * span + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        s = jnp.where(pos < seq_len, s, -1e30)
        m_prev = mx_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _mm_nn(
            p.astype(blk.dtype), blk[:, :value_width])
        mx_ref[:, :1] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
            o_ref.dtype)


def _latent_decode_call(q_abs, q_rope, kv_pages, page_table, seq_lens, *,
                        layer: int = 0, scale: float,
                        value_width: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Pallas latent decode. Same contract as latent_decode_attention_xla,
    except that a slot of length 0 (inactive: nobody reads it) gives zeros."""
    s_n, h, r = q_abs.shape
    value_width = r if value_width is None else value_width
    page, width = kv_pages.shape[3], kv_pages.shape[4]
    max_pages = page_table.shape[1]
    interpret = pallas_interpret(interpret)
    group = min(_LATENT_PAGES_PER_STEP, max_pages)
    kernel = functools.partial(_latent_decode_kernel, page=page, scale=scale,
                               group=group, value_width=value_width)
    layer = int(layer)

    def page_block(i):
        def index(s, j, pt, sl):
            last = jnp.maximum(sl[s] - 1, 0) // page
            return (layer, 0, pt[s, jnp.minimum(j * group + i, last)], 0, 0)

        return pl.BlockSpec((None, None, None, page, width), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_n, -(-max_pages // group)),
        in_specs=[pl.BlockSpec((None, h, width),
                               lambda s, j, pt, sl: (s, 0, 0))]
        + [page_block(i) for i in range(group)],
        out_specs=pl.BlockSpec((None, h, value_width),
                               lambda s, j, pt, sl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, value_width), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    with jax.named_scope("latent_decode_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n, h, value_width), q_abs.dtype),
            interpret=interpret,
            name="latent_decode_attention",
        )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
          _latent_query(q_abs, q_rope, width), *([kv_pages] * group))


def _latent_usable(q_abs, q_rope, kv_pages, page_table, seq_lens, **kw):
    """The Pallas latent path takes whole-tile pages (row a multiple of 128
    lanes, page of 8 sublanes), a latent that is whole lanes too, and heads
    that fill sublanes; the tiny test models take the generic path."""
    if getattr(q_abs, "ndim", 0) != 3 or getattr(kv_pages, "ndim", 0) != 5:
        return False
    if getattr(page_table, "ndim", 0) != 2 or kv_pages.shape[1] != 1:
        return False
    value_width = kw.get("value_width") or q_abs.shape[2]
    return (kv_pages.shape[4] % 128 == 0 and kv_pages.shape[3] % 8 == 0
            and value_width % 128 == 0 and q_abs.shape[2] % 128 == 0
            and q_abs.shape[1] % 8 == 0)


def _check_latent_decode_attention():
    """Validation case: the generic path against a numpy oracle and the
    Pallas kernel (interpreted) against both, on the second sub-layer."""
    import numpy as np

    rs = np.random.RandomState(11)
    s_n, h, r, dr, page, n_pages, max_pages, layer = 3, 8, 128, 32, 8, 10, 3, 1
    width = 256
    qa = rs.randn(s_n, h, r).astype(np.float32)
    qr = rs.randn(s_n, h, dr).astype(np.float32)
    kv = np.zeros((2, 1, n_pages, page, width), np.float32)
    kv[..., :r + dr] = rs.randn(2, 1, n_pages, page, r + dr)
    pt = np.stack([rs.choice(n_pages, max_pages, replace=False)
                   for _ in range(s_n)]).astype(np.int32)
    sl = np.array([5, 17, 24], np.int32)
    scale = 0.11
    want = np.zeros((s_n, h, r), np.float32)
    for i in range(s_n):
        rows = kv[layer, 0, pt[i]].reshape(-1, width)[:sl[i]]
        for hh in range(h):
            sc = (rows[:, :r] @ qa[i, hh] + rows[:, r:r + dr] @ qr[i, hh]) * scale
            p = np.exp(sc - sc.max())
            want[i, hh] = (p / p.sum()) @ rows[:, :r]
    args = tuple(jnp.asarray(a) for a in (qa, qr, kv, pt, sl))
    got = latent_decode_attention_xla(*args, layer=layer, scale=scale)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    assert _latent_usable(*args)
    got_pl = _latent_decode_call(*args, layer=layer, scale=scale)
    np.testing.assert_allclose(np.asarray(got_pl), want, rtol=1e-4, atol=1e-5)


def register_platform_attention() -> None:
    """Install flash attention as the TPU platform override for the generic
    dot_product_attention op, and register the paged decode-attention op
    (generic gather impl + Pallas TPU helper) — the cuDNN PlatformHelper
    pattern both times."""
    from deeplearning4j_tpu.ops.registry import registry
    from deeplearning4j_tpu.ops import validation as _validation

    reg = registry()

    if "paged_decode_attention" not in reg:
        reg.register(
            "paged_decode_attention", paged_decode_attention_xla,
            doc="decode-step attention over the block-paged KV pool "
                "(q:[S,H,D], kv_pages:[L,2,P,page,H*D], page_table:"
                "[S,max_pages], seq_lens:[S], layer= -> [S,H,D])")
        reg.register_platform("paged_decode_attention", "tpu",
                              _paged_decode_call, _paged_usable)
        _validation.add_case("paged_decode_attention",
                             _check_paged_decode_attention)

    if "latent_decode_attention" not in reg:
        reg.register(
            "latent_decode_attention", latent_decode_attention_xla,
            doc="decode-step latent (MLA, absorbed) attention over the latent "
                "paged pool (q_abs:[S,H,R], q_rope:[S,H,Dr], kv_pages:"
                "[L,1,P,page,W], page_table:[S,max_pages], seq_lens:[S], "
                "layer=, scale=, value_width= -> [S,H,R])")
        reg.register_platform("latent_decode_attention", "tpu",
                              _latent_decode_call, _latent_usable)
        _validation.add_case("latent_decode_attention",
                             _check_latent_decode_attention)

    def flash_dpa(q, k, v, mask=None, *, scaled: bool = True,
                  causal: bool = False,
                  dropout_rate: float = 0.0, dropout_rng=None):
        scale = (1.0 / math.sqrt(q.shape[-1])) if scaled else 1.0
        if dropout_rate > 0.0 and dropout_rng is None:
            raise ValueError(
                "dot_product_attention: dropout_rate > 0 requires dropout_rng "
                "(pass None rate for eval mode)")
        seed = rng_to_seed(dropout_rng) if dropout_rate > 0.0 else None
        rate = dropout_rate
        if q.ndim == 4:  # (B, H, T, D) + key mask broadcast (B, 1, 1, Tk)
            b, h, t, d = q.shape
            tk = k.shape[2]
            fold = lambda a: a.reshape(b * h, a.shape[2], a.shape[3])
            m = None
            if mask is not None:
                m = jnp.repeat(mask.reshape(b, tk).astype(jnp.float32), h, axis=0)
            out = flash_attention(fold(q), fold(k), fold(v), m, seed, scale,
                                  causal, None, None, None, rate)
            return out.reshape(b, h, t, q.shape[-1])
        m = None if mask is None else mask.reshape(q.shape[0], k.shape[1])
        return flash_attention(q, k, v, m, seed, scale, causal, None, None,
                               None, rate)

    def usable(q, k, v, mask=None, **kw):
        # Below the flash_min_t() threshold defer to the materialized
        # paths — PlatformHelper::isUsable (SURVEY §3.1). The crossover
        # came from the void 2026-07 rig's sweep and waits for the v5e's
        # own (ROADMAP.md D15, S4). EXCEPT with attention-prob dropout:
        # the generic path materializes a (T, T) bernoulli mask in HBM
        # while flash regenerates it in-kernel, so dropout takes the
        # kernel at any length (`bert-base.mlm-train` runs it at T = 512:
        # `flash_attn_roofline.train`, PERF.md).
        t_kv = k.shape[2] if q.ndim == 4 else k.shape[1]
        if t_kv < flash_min_t() and not kw.get("dropout_rate", 0.0):
            return False
        if kw.get("causal"):
            # the kernel's causal mask is start-aligned; only t_q == t_kv
            # agrees with the reference end-aligned convention
            t_q = q.shape[2] if q.ndim == 4 else q.shape[1]
            if t_q != t_kv:
                return False
        if q.ndim == 3:
            mask_ok = mask is None or (
                hasattr(mask, "ndim") and mask.ndim in (2, 3)
                and mask.shape[-1] == k.shape[1]
                and (mask.ndim == 2 or mask.shape[1] == 1))
        elif q.ndim == 4:
            # key-padding broadcast mask only: (B, 1, 1, Tk)
            mask_ok = mask is None or (
                hasattr(mask, "ndim") and mask.ndim == 4
                and mask.shape[1] == 1 and mask.shape[2] == 1
                and mask.shape[-1] == k.shape[2])
        else:
            return False
        return mask_ok and q.shape[-1] % 8 == 0

    if "dot_product_attention" in reg:
        reg.register_platform("dot_product_attention", "tpu", flash_dpa, usable)


# tuned-value invalidation: a fresh tuning table (autotune save, test
# reset) must drop the memoized flash_min_t parse along with the tables.
from deeplearning4j_tpu.ops import tuning as _tuning

_tuning.on_reset(reset_flash_min_t_cache)
