"""Expert parallelism — a Mixture-of-Experts layer sharded over an
``expert`` mesh axis with all_to_all token dispatch.

Reference parity: none — the reference has no MoE; this is the EXCEEDS-
reference expert-parallel axis the driver's multichip contract names
(tp/pp/dp/sp/ep). Design follows the public Switch-Transformer/GShard
recipe: top-1 token routing, per-expert capacity with drop-and-residual
overflow, all_to_all over ICI to move tokens to their expert's device and
back, plus the standard load-balancing auxiliary loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def init_moe_params(key, n_experts: int, d_model: int, d_hidden: int,
                    dtype=jnp.float32):
    """Router + per-expert MLP params, experts stacked on the leading axis
    (shard it over the 'expert' mesh axis)."""
    kr, k1, k2 = jax.random.split(key, 3)
    s1 = (2.0 / d_model) ** 0.5
    return {
        "router": (jax.random.normal(kr, (d_model, n_experts), dtype)
                   * (1.0 / d_model) ** 0.5),
        "W1": jax.random.normal(k1, (n_experts, d_model, d_hidden),
                                dtype) * s1,
        "W2": jax.random.normal(k2, (n_experts, d_hidden, d_model), dtype)
        * (2.0 / d_hidden) ** 0.5,
    }


def moe_spec(axis: str = "expert"):
    """PartitionSpecs for init_moe_params output: experts sharded, router
    replicated."""
    return {"router": P(), "W1": P(axis, None, None),
            "W2": P(axis, None, None)}


def moe_forward(mesh: Mesh, *, n_experts: int, capacity_factor: float = 1.25,
                axis: str = "expert"):
    """Build a jittable f(params, x) -> (y, aux_loss) running top-1 MoE
    with expert-parallel dispatch.

    x: (tokens, d_model), tokens divisible by the expert-axis size. Each
    device routes its local tokens, all_to_all ships them to their
    expert's device (capacity C per expert per source device), the local
    expert MLP runs ONE batched matmul pair, and a second all_to_all
    returns results. Dropped (over-capacity) tokens pass through
    residually, Switch-Transformer style.
    """
    ep = mesh.shape[axis]
    assert n_experts % ep == 0, (n_experts, ep)
    experts_per_device = n_experts // ep

    def per_device(params, x_local):
        t_local, d = x_local.shape
        cap = int(np.ceil(capacity_factor * t_local / n_experts))

        logits = x_local @ params["router"]              # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)          # (T,)
        gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]

        # load-balancing aux loss (Switch eq. 4): E * sum(frac_i * prob_i)
        frac = jnp.mean(jax.nn.one_hot(expert_idx, n_experts), axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = n_experts * jnp.sum(frac * mean_prob)

        # position of each token within its expert's capacity buffer
        onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)
        pos = jnp.take_along_axis(pos_in_expert, expert_idx[:, None],
                                  axis=1)[:, 0]
        keep = pos < cap

        # scatter tokens into (E, cap, d) send buffer
        buf = jnp.zeros((n_experts, cap, d), x_local.dtype)
        buf = buf.at[jnp.where(keep, expert_idx, 0),
                     jnp.where(keep, pos, 0)].add(
            jnp.where(keep[:, None], x_local, 0.0))

        # ship: regroup (E, cap, d) -> (ep, e_per_dev, cap, d), all_to_all
        send = buf.reshape(ep, experts_per_device, cap, d)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (ep_src, e_per_dev, cap, d) — tokens from every source
        # device for THIS device's experts
        tokens = recv.transpose(1, 0, 2, 3).reshape(
            experts_per_device, ep * cap, d)
        w1 = params["W1"]                                # (e_per_dev, d, h)
        w2 = params["W2"]
        h = jax.nn.relu(jnp.einsum("etd,edh->eth", tokens, w1))
        out = jnp.einsum("eth,ehd->etd", h, w2)
        out = out.reshape(experts_per_device, ep, cap, d).transpose(
            1, 0, 2, 3)
        back = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        back = back.reshape(n_experts, cap, d)

        # gather each token's result; dropped tokens pass through
        got = back[jnp.where(keep, expert_idx, 0),
                   jnp.where(keep, pos, 0)]
        y = jnp.where(keep[:, None], gate[:, None] * got, x_local)
        return y, aux.reshape(1)

    def run(params, x):
        f = shard_map(
            per_device, mesh=mesh,
            in_specs=(moe_spec(axis), P(axis, None)),
            out_specs=(P(axis, None), P(axis)),
            )
        y, aux = f(params, x)
        return y, jnp.mean(aux)

    return run
