"""Long-context attention: sequence parallelism over a device mesh.

The framework ships BOTH first-class strategies (the capability the
reference's truncated-BPTT never had):
  * ring attention  — K/V shards rotate via ppermute, online-softmax
                      accumulation; any head count, N hops
  * Ulysses         — two all-to-alls re-shard sequence → heads → sequence;
                      one pass of dense attention per device

Run: python examples/long_context_attention.py
(8 virtual CPU devices so it runs anywhere; the same code spans real
chips over ICI)"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from deeplearning4j_tpu.parallel import ring_attention, ulysses_attention  # noqa: E402


def main():
    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("seq",))
    b, h, t, d = 1, 8, 64 * n, 32  # sequence N× one device's share
    r = np.random.RandomState(0)
    q = r.randn(b, h, t, d).astype(np.float32)
    k = r.randn(b, h, t, d).astype(np.float32)
    v = r.randn(b, h, t, d).astype(np.float32)

    spec4 = NamedSharding(mesh, P(None, None, "seq", None))
    uly = np.asarray(ulysses_attention(
        jax.device_put(jnp.asarray(q), spec4),
        jax.device_put(jnp.asarray(k), spec4),
        jax.device_put(jnp.asarray(v), spec4), mesh=mesh, causal=True))

    spec3 = NamedSharding(mesh, P(None, "seq", None))
    ring = np.asarray(ring_attention(
        jax.device_put(jnp.asarray(q.reshape(b * h, t, d)), spec3),
        jax.device_put(jnp.asarray(k.reshape(b * h, t, d)), spec3),
        jax.device_put(jnp.asarray(v.reshape(b * h, t, d)), spec3),
        mesh=mesh, causal=True)).reshape(b, h, t, d)

    diff = float(np.abs(uly - ring).max())
    print(f"sequence length {t} sharded over {n} devices")
    if diff >= 1e-3:
        raise SystemExit(
            f"ulysses vs ring max|Δ| = {diff:.2e} — strategies DISAGREE")
    print(f"ulysses vs ring max|Δ| = {diff:.2e}  (strategies agree)")


if __name__ == "__main__":
    main()
