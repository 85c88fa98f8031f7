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

from deeplearning4j_tpu.ops import exec_op
from deeplearning4j_tpu.ops.pallas_grouped import grouped_row_tile


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


# ---------------------------------------------------------------------------
# Served expert layer: top-k over routed + zero-compute experts, one share
# ---------------------------------------------------------------------------


def grouped_rows(t: int, top_k: int, count: int, outputs: int):
    """``(fit, n_rows)`` of a step of ``t`` tokens: all its (token, pick)
    rows, and the head of the sorted rows that the grouped products take
    while the held rows fit it: twice what even routing over ``outputs``
    router outputs gives the ``count`` held experts, in whole 128-row
    tiles. ``fit >= n_rows``: there is no head, every step takes all rows."""
    n_rows = t * top_k
    twice = -(-2 * n_rows * count // outputs)
    return 128 * -(-twice // 128), n_rows


def grouped_path(stats, t: int, *, top_k: int, outputs: int):
    """``(path, tile)`` of a program's grouped products, on the host, from
    its expert layers' statistics (``stats``: (layers, held experts + 2), the
    tokens a held expert first: what the step's read already brings) and the
    shapes ``moe_topk_share`` saw (``t`` tokens, ``top_k`` of ``outputs``
    router outputs): ``"head"`` where every layer's held rows fitted the
    head of the sorted rows, ``"all"`` where a layer took all ``t * top_k``
    (or there is no head), and the row tile the products of that many rows
    ran with (``grouped_row_tile``)."""
    held = np.asarray(stats)[:, :-2]
    fit, n_rows = grouped_rows(t, top_k, held.shape[1], outputs)
    head = fit < n_rows and int(held.sum(axis=1).max(initial=0)) <= fit
    return "head" if head else "all", grouped_row_tile(fit if head else n_rows)


def moe_topk_share(params, u, *, top_k: int, n_routed: int, n_zero: int,
                   scale: float, held, bias=None, valid=None,
                   score: str = "softmax", renormalise: bool = False):
    """One chip's share of a top-``k`` expert layer with zero-compute
    (identity) experts, for serving: no capacity and no dropped token,
    whatever the imbalance.

    params: ``router`` (d, n_routed + n_zero) and the HELD experts' SwiGLU
    weights ``Wg``/``Wu`` (count, d, w) and ``Wd`` (count, w, d); u: (T, d);
    ``held = (first, count)``: the routed experts ``first .. first+count-1``
    live here. The router's scores ``s`` (float32) run over ALL outputs:
    ``score="softmax"`` over them, or ``"sigmoid"`` of each logit alone; the
    chosen are the top ``k`` of ``s + bias`` (the bias moves the choice, not
    the weight); a chosen output's weight is ``scale * s``, and with
    ``renormalise`` ``scale * s / sum of the chosen s``, whoever holds them.
    The result is the partial sum this chip can give: its held experts' terms
    and the zero experts' ``w * u`` (an identity needs no owner); the absent
    experts' terms are left out. The (token, pick) rows of held experts are
    sorted by expert and go through the registry's ``grouped_swiglu``
    (``ops/pallas_grouped.py``: row tiles that fit the rows a group holds):
    every shape is static, so the caller's program compiles once, and a row
    is computed by its own expert only. The product
    runs over the head of the sorted rows where the held rows fit it (twice
    what even routing gives this rank) and over all of them where not.

    ``u`` may be wider than the experts' weights (float32 beside bfloat16):
    the router reads it as given, the experts read it in their weights' type,
    and the result has ``u``'s type.

    ``valid``: (T,) bool, the tokens that count: padding and idle slots take
    no row of the grouped product (nobody reads their result: a prompt's 300
    padded positions are one token over and over, route alike and would
    swamp one expert) and stay out of the statistics. Returns ``(y (T, d), stats (count + 2,) int32)``:
    tokens a held expert, then the picks that went to zero experts and to
    absent experts."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score {score!r}: 'softmax' or 'sigmoid'")
    first, count = held
    t, d = u.shape
    with jax.named_scope("moe_route"):
        logits = jnp.dot(u.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
             else jax.nn.sigmoid(logits))
        ranked = s if bias is None else s + bias.astype(jnp.float32)
        _, chosen = jax.lax.top_k(ranked, top_k)                  # (T, k)
        weight = jnp.take_along_axis(s, chosen, axis=-1)          # (T, k)
        if renormalise:
            weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
        weight = scale * weight
        is_zero = chosen >= n_routed
        local = chosen - first
        is_held = (local >= 0) & (local < count) & ~is_zero
        seen = (jnp.ones((t,), bool) if valid is None else valid)[:, None]
        per_expert = jnp.zeros((count + 1,), jnp.int32).at[
            jnp.where(is_held & seen, local, count)].add(1)[:count]
        stats = jnp.concatenate([
            per_expert,
            jnp.sum(is_zero & seen, dtype=jnp.int32)[None],
            jnp.sum(~is_zero & ~is_held & seen, dtype=jnp.int32)[None]])
        w_zero = jnp.sum(jnp.where(is_zero, weight, 0.0), axis=-1)
    y = w_zero[:, None] * u.astype(jnp.float32)
    if count == 0:
        return y.astype(u.dtype), stats
    with jax.named_scope("moe_experts"):
        # rows (token, pick), the held experts' first and in expert order
        # (``per_expert`` is each group's size); the others form no group
        # and give nought
        group = jnp.where(is_held & seen, local, count).reshape(-1)
        order = jnp.argsort(group, stable=True)
        sizes = per_expert
        row_w = jnp.where(group[order] < count, weight.reshape(-1)[order], 0.0)

        def experts(n):
            """The first ``n`` sorted rows through the grouped products."""
            token, w_n = order[:n] // top_k, row_w[:n, None]
            rows = u[token].astype(params["Wg"].dtype)
            out = exec_op("grouped_swiglu", rows, params["Wg"], params["Wu"],
                          params["Wd"], sizes)
            # a row past the groups holds whatever the product left there
            out = jnp.where(w_n != 0.0, out * w_n, 0.0)
            return jnp.zeros((t, d), jnp.float32).at[token].add(out)

        # The held rows are the sorted rows' head, and a rank holds few of
        # the experts: under even routing t * k * count / outputs rows, 32 of
        # a decode step's 1536. The grouped product works in tiles of rows,
        # so it is given twice the expected rows (whole 128-row tiles) where
        # the held rows fit, and all t * k rows where they do not: nothing is
        # dropped whatever the imbalance, and every shape is static.
        fit, n_rows = grouped_rows(t, top_k, count, n_routed + n_zero)
        if fit < n_rows:
            y = y + jax.lax.cond(jnp.sum(sizes) <= fit,
                                 lambda: experts(fit), lambda: experts(n_rows))
        else:
            y = y + experts(n_rows)
    return y.astype(u.dtype), stats
