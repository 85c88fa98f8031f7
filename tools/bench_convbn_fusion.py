"""Measure the Pallas fused BN→matmul→stats kernel vs the unfused XLA chain.

The round-4 perf analysis claimed ResNet-50 is bound by BN activation
traffic but could not prove it (cost_analysis bytes overcount fusion
reuse).  This tool produces the kernel evidence: for each real
bottleneck 1×1-conv shape of ResNet-50 @ b128 it times

  * the unfused chain   (BN-affine+relu pass → XLA matmul → stats pass)
  * the Pallas kernel   (one HBM pass, prologue/epilogue fused)

on the real chip (device-side lax.scan loop: one dispatch per timed
window), and prints XLA cost-analysis bytes for both so the traffic delta is explicit.

Besides the human table, the tool emits a TUNING-TABLE FRAGMENT (the
ops/tuning.py dl4j_tpu_tuning_v1 schema): the best-measured Pallas block_m
per shape bucket. Fragments are NOT loaded automatically — merge one into
a committed table under ops/tuning_tables/ via ``TuningTable.merge`` so
the kernel's block picker uses the measured winners (docs/KERNELS.md
§ Re-tuning). Fragment path: SWEEP_TABLE_OUT env, default
<tuning dir>/fragment_convbn_<device_kind>.json.

Usage: python tools/bench_convbn_fusion.py [--iters 50] [--blocks 256,512]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# (label, M, K, N) — every distinct 1×1 conv+BN shape in ResNet-50 @ b128
SHAPES = [
    ("s1_c1", 128 * 56 * 56, 256, 64),
    ("s1_c3", 128 * 56 * 56, 64, 256),
    ("s2_c1", 128 * 28 * 28, 512, 128),
    ("s2_c3", 128 * 28 * 28, 128, 512),
    ("s3_c1", 128 * 14 * 14, 1024, 256),
    ("s3_c3", 128 * 14 * 14, 256, 1024),
    ("s4_c1", 128 * 7 * 7, 2048, 512),
    ("s4_c3", 128 * 7 * 7, 512, 2048),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--shapes", default=None,
                    help="comma-separated labels to run (default: all)")
    ap.add_argument("--blocks", default="0,256,512",
                    help="comma-separated block_m candidates for the Pallas "
                         "kernel (0 = the kernel's own pick)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_convbn import (
        fused_bn_matmul_stats, reference_bn_matmul_stats)

    want = set(args.shapes.split(",")) if args.shapes else None
    results = []
    for label, m, k, n in SHAPES:
        if want and label not in want:
            continue
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(m, k).astype(np.float32)).astype(jnp.bfloat16)
        sc = jnp.asarray(r.rand(k).astype(np.float32) + 0.5)
        sh = jnp.asarray(r.randn(k).astype(np.float32) * 0.1)
        w = jnp.asarray((r.randn(k, n) * k ** -0.5).astype(np.float32)).astype(jnp.bfloat16)
        ss = jnp.asarray(r.randn(n).astype(np.float32) * 0.1)

        def make(fn):
            @jax.jit
            def bench(x, sc, sh, w, ss, eps):
                # chain each iteration through the (tiny) stats vector with a
                # runtime-zero eps, and probe one column of z — XLA cannot
                # fold either away (a literal *0 gets DCE'd and the first
                # version of this bench measured empty scans)
                def body(carry, _):
                    z, mean, var = fn(x, sc, sh, w, carry)
                    probe = jnp.sum(z[:, :1].astype(jnp.float32))
                    return carry + eps * (mean + var + probe), probe
                c, ps = jax.lax.scan(body, ss, None, length=args.iters)
                return jnp.sum(c), ps[-1]
            return bench

        def run(bench):
            zero = jnp.float32(0.0)
            _ = jax.block_until_ready(bench(x, sc, sh, w, ss, zero))  # compile
            t0 = time.perf_counter()
            _ = jax.block_until_ready(bench(x, sc, sh, w, ss, zero))
            return (time.perf_counter() - t0) / args.iters * 1e3

        def cost_bytes(fn):
            lowered = jax.jit(lambda x, sc, sh, w, ss: fn(x, sc, sh, w, ss)
                              ).lower(x, sc, sh, w, ss)
            c = lowered.compile().cost_analysis()
            if isinstance(c, list):
                c = c[0]
            return c.get("bytes accessed", 0.0)

        import functools
        ref = functools.partial(reference_bn_matmul_stats, materialize=True)
        t_ref = run(make(ref))
        # block-candidate sweep for the Pallas kernel: the best block_m per
        # shape bucket lands in the tuning fragment. Candidates that do not
        # divide this shape's m are skipped; if none survive, fall back to
        # the kernel's own pick (0) so one ragged shape cannot kill the run
        t_fused, best_bm = None, 0
        cands = [int(b) for b in args.blocks.split(",")]
        if not any(not bm or m % bm == 0 for bm in cands):
            cands = [0]
        for bm in cands:
            if bm and m % bm:
                continue
            t = run(make(functools.partial(fused_bn_matmul_stats,
                                           block_m=bm)))
            if t_fused is None or t < t_fused:
                t_fused, best_bm = t, bm
        by_ref = cost_bytes(ref)
        # cost analysis must describe the SAME configuration that was timed
        by_fused = cost_bytes(functools.partial(fused_bn_matmul_stats,
                                                block_m=best_bm))
        # one-pass ideal traffic: read x + w, write z (+ stats, negligible)
        ideal = (m * k + k * n + m * n) * 2
        row = {"shape": label, "m": m, "k": k, "n": n,
               "xla_ms": round(t_ref, 3), "pallas_ms": round(t_fused, 3),
               "best_block_m": best_bm,
               "speedup": round(t_ref / t_fused, 3),
               "xla_bytes_mb": round(by_ref / 1e6, 1),
               "pallas_bytes_mb": round(by_fused / 1e6, 1),
               "ideal_bytes_mb": round(ideal / 1e6, 1)}
        results.append(row)
        print(json.dumps(row))

    if results:
        tot_x = sum(r["xla_ms"] for r in results)
        tot_p = sum(r["pallas_ms"] for r in results)
        print(json.dumps({"total_xla_ms": round(tot_x, 2),
                          "total_pallas_ms": round(tot_p, 2),
                          "speedup": round(tot_x / tot_p, 3)}))

        # tuning-table fragment (ops/tuning.py schema): measured block_m
        # winners per shape bucket for this device kind
        from deeplearning4j_tpu.ops import tuning

        kind = tuning.normalize_device_kind(jax.devices()[0].device_kind)
        frag = tuning.TuningTable(device_kind=kind)
        for r in results:
            if r["best_block_m"]:
                frag.set_block("fused_bn_matmul_stats",
                               tuning.bucket_mkn(r["m"], r["k"], r["n"]),
                               "block_m", r["best_block_m"])
        out_path = os.environ.get(
            "SWEEP_TABLE_OUT",
            os.path.join(tuning.tuning_dir(),
                         f"fragment_convbn_{kind}.json"))
        frag.save(out_path)
        print(f"tuning fragment -> {out_path}")


if __name__ == "__main__":
    main()
