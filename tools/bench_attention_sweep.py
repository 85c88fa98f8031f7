"""Flash-attention honesty sweep (round-4 verdict item 6).

Benchmarks the Pallas flash kernel against BOTH competitors across
T x {causal, full}, fwd+bwd in bf16:
  * jax.nn.dot_product_attention (implementation='xla') — the fused XLA
    path and the honest competitor,
  * our own generic composition (_reference_attention) — the historical
    baseline the 1.95x claim was measured against.

Prints one row per shape and emits a TUNING-TABLE FRAGMENT (the
ops/tuning.py dl4j_tpu_tuning_v1 schema) with the measured flash-vs-XLA
crossover for this device kind. Fragments are NOT loaded automatically:
merge one into the committed default table
(deeplearning4j_tpu/ops/tuning_tables/<kind>.json) or into the cache
table the loader actually reads (<cache dir>/<device_kind>.json) via
``TuningTable.merge`` — docs/KERNELS.md § Re-tuning. DL4J_TPU_FLASH_MIN_T
still overrides everything. Fragment path: SWEEP_TABLE_OUT env, default
<cache dir>/fragment_attention_<device_kind>.json.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_shape(t: int, causal: bool, iters: int = None):
    if iters is None:
        iters = int(os.environ.get("SWEEP_ITERS", "50"))
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention, _reference_attention)

    bh, d = 8, 64
    b, h = 2, 4  # bh = b*h for the jax.nn API's (B, T, N, H) layout
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(bh, t, d).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(r.randn(bh, t, d).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(r.randn(bh, t, d).astype(np.float32)).astype(jnp.bfloat16)
    scale = d ** -0.5

    def timed(loss_fn, *args, reps: int = 3):
        """DCE/hoist-proof device loop (round-5 verdict item 6: the old
        harness multiplied grads by a LITERAL zero, which XLA folds, and
        left the loop body loop-invariant, which XLA hoists — the
        non-monotonic competitor numbers were measurement artifacts).
        The carry threads through grad(carry, ...) with a RUNTIME-zero eps,
        and each timed run is fenced by ``jax.block_until_ready``. Returns
        (min_ms, mean_ms, std_ms) over ``reps`` timed runs."""
        grad = jax.grad(loss_fn, argnums=tuple(range(len(args))))

        @jax.jit
        def run(eps, *a):
            def body(carry, _):
                g = grad(carry, *a[1:])
                acc = carry + (eps * g[0].astype(jnp.float32)
                               ).astype(carry.dtype)
                tail = sum(jnp.sum(gi.astype(jnp.float32)) for gi in g[1:])
                acc = acc + (eps * tail).astype(carry.dtype)
                return acc, ()

            qf, _ = jax.lax.scan(body, a[0], None, length=iters)
            return jnp.sum(qf.astype(jnp.float32))

        zero = jnp.float32(0.0)
        jax.block_until_ready(run(zero, *args))  # compile + warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run(zero, *args))
            times.append((time.perf_counter() - t0) / iters * 1e3)
        return (min(times), float(np.mean(times)), float(np.std(times)))

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, None, scale, causal,
                                       None, None, None, 0.0)
                       .astype(jnp.float32) ** 2)

    def gen_loss(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, scale=scale,
                                            causal=causal)
                       .astype(jnp.float32) ** 2)

    # jax.nn.dot_product_attention wants (B, T, N, H)
    q4 = q.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    k4 = k.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    v4 = v.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    def xla_loss(q4, k4, v4):
        out = jax.nn.dot_product_attention(q4, k4, v4, scale=scale,
                                           is_causal=causal,
                                           implementation="xla")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    t_flash = timed(flash_loss, q, k, v)
    t_gen = timed(gen_loss, q, k, v)
    t_xla = timed(xla_loss, q4, k4, v4)
    return t_flash, t_xla, t_gen


def main() -> None:
    import jax

    seqs = [int(s) for s in os.environ.get(
        "SWEEP_T", "1024,2048,4096,8192,16384").split(",")]
    wins = {}  # T -> flash beat XLA in BOTH causal modes
    print(f"device: {jax.devices()[0].device_kind}  (bh=8, d=64, bf16, "
          f"fwd+bwd, ms per call)")
    print(f"{'T':>6} {'causal':>6} {'flash':>9} {'xla':>9} {'generic':>9} "
          f"{'flash/xla':>9}")
    for t in seqs:
        for causal in (True, False):
            (f_min, _, f_std), (x_min, _, x_std), (g_min, _, _) = \
                bench_shape(t, causal)
            wins[t] = wins.get(t, True) and x_min >= f_min
            print(f"{t:>6} {str(causal):>6} {f_min:>9.3f} {x_min:>9.3f} "
                  f"{g_min:>9.3f} {x_min / f_min:>9.2f}x  "
                  f"(std f={f_std:.3f} x={x_std:.3f})")

    # tuning-table fragment (ops/tuning.py schema): the measured crossover
    # is the smallest swept T where flash beats XLA in BOTH causal modes;
    # if flash never wins, 2x the largest point (pessimistic, re-measurable)
    from deeplearning4j_tpu.ops import tuning

    kind = tuning.normalize_device_kind(jax.devices()[0].device_kind)
    frag = tuning.TuningTable(device_kind=kind)
    crossover = next((t for t in sorted(wins) if wins[t]), 2 * max(seqs))
    frag.set("dot_product_attention", "flash_min_t", int(crossover))
    out_path = os.environ.get(
        "SWEEP_TABLE_OUT",
        os.path.join(tuning.tuning_dir(), f"fragment_attention_{kind}.json"))
    frag.save(out_path)
    print(f"tuning fragment (flash_min_t={crossover}) -> {out_path}")


if __name__ == "__main__":
    main()
