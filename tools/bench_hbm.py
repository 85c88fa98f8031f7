"""Measure the chip's ACTUAL deliverable HBM bandwidth: a donated
read+write streaming pass (c = c + eps under lax.scan) at several sizes,
each timed window fenced by ``jax.block_until_ready``.

Why it matters: a roofline share needs the bandwidth the chip delivers, not
only the v5e spec sheet's 819 GB/s. The 380-414 GB/s that
docs/PERF_ANALYSIS.md builds on was read on a rig that is gone; on the
machine builders reach now it is not measured yet (ROADMAP S8).

Usage: python tools/bench_hbm.py
"""

from __future__ import annotations

import json
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    rows = []
    for label, dtype, shape, iters in [
        ("128MB_bf16", jnp.bfloat16, (64, 1024, 1024), 100),
        ("512MB_bf16", jnp.bfloat16, (256, 1024, 1024), 50),
        ("1GB_bf16", jnp.bfloat16, (512, 1024, 1024), 50),
        ("2GB_bf16", jnp.bfloat16, (1024, 1024, 1024), 30),
        ("512MB_f32", jnp.float32, (128, 1024, 1024), 50),
    ]:
        x = jnp.zeros(shape, dtype)

        @jax.jit
        def run(eps, x, iters=iters):
            def body(c, _):
                return c + eps, ()

            c, _ = jax.lax.scan(body, x, None, length=iters)
            return jnp.sum(c[:1, :1, :8].astype(jnp.float32))

        z = jnp.asarray(0.0, dtype)
        jax.block_until_ready(run(z, x))
        jax.block_until_ready(run(z, x))
        t0 = time.perf_counter()
        jax.block_until_ready(run(z, x))
        per = (time.perf_counter() - t0) / iters
        bw = x.nbytes * 2 / per / 1e9  # read + write
        rows.append({"case": label, "ms_per_pass": round(per * 1e3, 3),
                     "gb_per_s": round(bw, 1)})
        print(json.dumps(rows[-1]))
    peak = max(r["gb_per_s"] for r in rows)
    print(json.dumps({"measured_peak_stream_gb_s": peak,
                      "device": jax.devices()[0].device_kind,
                      "spec_sheet_gb_s": 819}))


if __name__ == "__main__":
    main()
