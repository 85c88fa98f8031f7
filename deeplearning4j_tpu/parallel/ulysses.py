"""Ulysses-style all-to-all sequence parallelism.

The second first-class long-context strategy next to ring attention
(parallel/ring_attention.py). Where the ring rotates K/V shards and keeps an
online-softmax accumulator, Ulysses (DeepSpeed-Ulysses, Jacobs et al. 2023)
re-shards with two all-to-alls: activations enter sharded over SEQUENCE,
an all-to-all re-shards attention inputs over HEADS (each device then holds
its heads' FULL sequence and runs ordinary dense/flash attention), and a
second all-to-all restores sequence sharding afterwards.

Trade-offs vs the ring (why both exist, as in the reference ecosystem):
  * comm volume: Ulysses moves q,k,v,out once each (4·T/N·D per device per
    layer) regardless of N; the ring moves k,v N−1 times.
  * constraint: Ulysses needs num_heads % N == 0; the ring has no head
    constraint but serializes N hops.
On TPU both ride ICI as XLA collectives: ``all_to_all`` here, ``ppermute``
there — never hand-written transports (SURVEY §3.5 comm-backend row).

Usage (inputs sharded (B, H, T/N, D) over axis 'seq'):
    out = ulysses_attention(q, k, v, mesh=mesh, axis='seq')
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _ulysses_local(q, k, v, *, axis_name: str, scale: float, causal: bool):
    """Per-shard body (under shard_map). q/k/v: (B, H, T_local, D) — the
    LOCAL sequence shard of all heads. Re-shards to all heads' full
    sequence for H/N local heads, attends densely, re-shards back."""
    def seq_to_heads(x):
        # (B, H, T/N, D) -> (B, H/N, T, D): ONE tiled all-to-all — head
        # chunk j goes to device j, and each device concatenates its head
        # chunk from every source along the sequence axis in source
        # (= sequence-shard) order
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        # inverse: (B, H/N, T, D) -> (B, H, T/N, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32) * scale,
                   kh.astype(jnp.float32))
    if causal:
        t = s.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention(q, k, v, *, mesh: Mesh, axis: str = "seq",
                      scale: Optional[float] = None, causal: bool = False):
    """All-to-all sequence-parallel attention. q/k/v: (B, H, T, D) GLOBAL
    shapes, sharded over T on ``axis``. num_heads must divide by the axis
    size."""
    from jax import shard_map

    n = mesh.shape[axis]
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by the '{axis}' axis "
            f"size ({n}) — use ring_attention for head-indivisible meshes")
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, None, axis, None)
    fn = shard_map(
        lambda a, b, c: _ulysses_local(a, b, c, axis_name=axis, scale=sc,
                                       causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
