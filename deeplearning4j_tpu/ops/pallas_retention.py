"""Power retention (degree 2): a linear-attention layer whose cache is a
fixed-size state a key/value head, not rows a token.

With ``w[t, j] = ((q_t . k_j) / sqrt d)^2 * exp(sum_{s=j+1..t} g_s)`` (``g``
the log of a gate in (0, 1), one a key/value head) a query head reads::

    y_t = sum_{j<=t} w[t, j] v_j / (sum_{j<=t} w[t, j] + eps)

and since ``(q . k)^2 / d = phi(q) . phi(k)`` for the symmetric second power
``phi``, the same thing is a recurrence over a state ``S (rows of phi, dv)``
and a normaliser ``z``::

    S_t = exp(g_t) S_{t-1} + phi(k_t) v_t^T     z_t = exp(g_t) z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

**How phi is laid out** (:func:`power_phi`): row ``o`` of ``d // 2 + 1`` holds
``c_o u_a u_{(a + o) mod d} / sqrt d`` for ``a = 0..d-1``, ``c_0 = c_{d/2} = 1``
and ``sqrt 2`` between. Every unordered pair ``{a, b}`` lies in exactly one
row but the pairs half a turn apart, which row ``d / 2`` holds twice at
weight 1 (the same as once at ``sqrt 2``): ``d (d + 2) / 2`` values where the
symmetric power has ``d (d + 1) / 2`` (8320 for 8256 at d 128), each row a
whole vector of lanes and a lane rotation of ``u`` away from the next.

A state is ``S (rows, dv, d)``: ``d`` along the lanes, so that both the
update (``phi(k)``'s row) and the read (``phi(q)``'s row) broadcast a row
over sublanes, and the read's sum over ``a`` is one reduction over lanes at
the end.

Ops: ``power_retention_decode`` (one token for every slot against the pool of
states, updated in place: generic ``jax.numpy`` body everywhere, a Pallas
kernel ``retention_decode`` on the TPU that decays, updates and reads a state
in ONE pass over it) and ``power_retention_prefill`` (one prompt: the
quadratic form for its outputs and one product ``phi(K)^T [V, 1]`` for the
state it leaves). docs/KERNELS.md § Power retention has the timings."""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.registry import pallas_interpret

_HIGHEST = jax.lax.Precision.HIGHEST


def phi_rows(d: int) -> int:
    """Rows of :func:`power_phi` over a head of (even) width ``d``."""
    if d % 2:
        raise ValueError(f"power retention needs an even head width, got {d}")
    return d // 2 + 1


def power_phi(u):
    """(..., d) -> (..., d // 2 + 1, d) float32 with ``phi(q) . phi(k) =
    (q . k)^2 / d`` (the sum over both last axes)."""
    d = u.shape[-1]
    rows = phi_rows(d)
    u = u.astype(jnp.float32)
    twice = jnp.concatenate([u, u], axis=-1)
    turned = jnp.stack([twice[..., o:o + d] for o in range(rows)], axis=-2)
    c = np.full((rows, 1), math.sqrt(2.0), np.float32)
    c[0] = c[-1] = 1.0
    return turned * u[..., None, :] * (c / math.sqrt(d))


# ------------------------------------------------------------------ decode


def power_retention_decode_xla(state, norm, q, k, v, g, active, *,
                               layer: int = 0, eps: float = 1e-6):
    """Generic path. ``state`` (slots, L, Hkv, rows, dv, d) and ``norm``
    (slots, L, Hkv, rows, d) float32: the pool, of which layer ``layer`` is
    decayed, updated and read; q (slots, Hq, d), k (slots, Hkv, d), v (slots,
    Hkv, dv), g (slots, Hkv) the log of the decay, ``active`` (slots,).
    Query head ``a`` reads the state of key/value head ``a // (Hq // Hkv)``.
    Returns ``(state, norm, y (slots, Hq, dv), den (slots, Hq), absmax)``:
    the normalised read, the normaliser ``phi(q)^T z`` before ``eps`` and the
    largest ``|S|`` entry of an active slot's updated state."""
    s_n, hq, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    with jax.named_scope("retention_decode"):
        # a slot that sits the step out: a decay of 1 and phi(k) nought, so
        # that its state stays to the last bit
        on = active.astype(bool)
        decay = jnp.where(on[:, None], jnp.exp(g.astype(jnp.float32)), 1.0)
        pk = power_phi(k) * on[:, None, None, None].astype(jnp.float32)
        pq = power_phi(q).reshape(s_n, hkv, hq // hkv, phi_rows(d), d)
        s_new = (decay[..., None, None, None] * state[:, layer]
                 + pk[:, :, :, None, :]
                 * v.astype(jnp.float32)[:, :, None, :, None])
        z_new = decay[..., None, None] * norm[:, layer] + pk
        num = jnp.einsum("shgra,shrva->shgv", pq, s_new, precision=_HIGHEST)
        den = jnp.einsum("shgra,shra->shg", pq, z_new, precision=_HIGHEST)
        y = num / (den + eps)[..., None]
        absmax = jnp.max(jnp.abs(s_new) * active.astype(jnp.float32)[
            :, None, None, None, None])
        return (state.at[:, layer].set(s_new), norm.at[:, layer].set(z_new),
                y.reshape(s_n, hq, dv), den.reshape(s_n, hq), absmax)


def _rows_per_step(rows: int) -> int:
    """Rows of phi a grid step of the kernel takes: the largest divisor of
    ``rows`` up to 16 (13 of 65 at d 128: blocks of 852 KB)."""
    return max(r for r in range(1, min(rows, 16) + 1) if rows % r == 0)


def _retention_decode_kernel(decay_ref, q_ref, k_ref, vb_ref, s_ref, z_ref,
                             so_ref, zo_ref, y_ref, den_ref, amax_ref,
                             acc_ref, pq_ref, pk_ref, *, step_rows: int,
                             group: int, hkv: int):
    """One (slot, key/value head, block of phi's rows) grid step. At a
    head's first block the rows of ``phi(k)`` and of the group's ``phi(q)``
    are made in fast memory, a lane rotation and a product each (``pq_ref``
    (rows, group, d), ``pk_ref`` (rows, d)), and the normaliser is decayed,
    updated and read. The block of the state ``(step_rows, dv, d)`` is
    decayed, gets its rows of ``phi(k) v^T``, is written back where it lay
    and is read by the group's query heads before it leaves fast memory. A
    slab of 8 values of ``dv`` at a time, so that a slab's running sums stay
    in registers; the sums over phi's rows collect in ``acc_ref (group, dv,
    d)`` across the blocks and are summed over the lanes once, at the last
    block."""
    s_idx, h_idx, blk = (pl.program_id(0), pl.program_id(1),
                         pl.program_id(2))
    decay = decay_ref[s_idx * hkv + h_idx]
    dv, d = vb_ref.shape
    rows = z_ref.shape[0]
    row0 = blk * step_rows

    @pl.when(blk == 0)
    def _first():
        scale = d ** -0.25
        q, k = q_ref[...] * scale, k_ref[...] * scale   # (group, d), (1, d)
        q2, k2 = math.sqrt(2.0) * q, math.sqrt(2.0) * k
        den = jnp.zeros((group, d), jnp.float32)
        for o in range(rows):
            if o == 0:                       # the squares
                pq, pk = q * q, k * k
            else:                            # u_a u_(a + o), a lane rotation
                whole = o == rows - 1        # half a turn: each pair twice
                pq = (q if whole else q2) * pltpu.roll(q, d - o, 1)
                pk = (k if whole else k2) * pltpu.roll(k, d - o, 1)
            pq_ref[o, :group, :] = pq
            pk_ref[o:o + 1, :] = pk
            z_new = decay * z_ref[o:o + 1, :] + pk
            zo_ref[o:o + 1, :] = z_new
            den = den + pq * z_new
        den_ref[...] = den
        acc_ref[...] = jnp.zeros_like(acc_ref)
        amax_ref[...] = jnp.zeros_like(amax_ref)

    def slab(j, carry):
        rows8 = pl.ds(pl.multiple_of(j * 8, 8), 8)
        vb = vb_ref[rows8, :]
        sums = [jnp.zeros((8, d), jnp.float32) for _ in range(group)]
        amax = jnp.zeros((8, d), jnp.float32)
        for i in range(step_rows):
            new = (decay * s_ref[i, rows8, :]
                   + vb * pk_ref[pl.ds(row0 + i, 1), :])
            so_ref[i, rows8, :] = new
            amax = jnp.maximum(amax, jnp.abs(new))
            for gi in range(group):
                sums[gi] = sums[gi] + new * pq_ref[row0 + i, gi:gi + 1, :]
        for gi in range(group):
            acc_ref[gi, rows8, :] += sums[gi]
        amax_ref[...] = jnp.maximum(amax_ref[...], amax)
        return carry

    jax.lax.fori_loop(0, dv // 8, slab, 0)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _last():
        for gi in range(group):
            y_ref[gi:gi + 1, :] = jnp.sum(acc_ref[gi].T, axis=0,
                                          keepdims=True)


def power_retention_decode_pallas(state, norm, q, k, v, g, active, *,
                                  layer: int = 0, eps: float = 1e-6,
                                  interpret: Optional[bool] = None):
    """The kernel ``retention_decode``: the contract of
    :func:`power_retention_decode_xla`, the pool aliased input to output so
    that the program updates it where it lies. ``phi`` of the step's vectors
    is made inside the kernel."""
    s_n, hq, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    group, rows = hq // hkv, phi_rows(d)
    step_rows = _rows_per_step(rows)
    layer = int(layer)
    with jax.named_scope("retention_decode"):
        on = active.astype(bool)
        decay = jnp.where(on[:, None], jnp.exp(g.astype(jnp.float32)), 1.0)
        # a slot that sits the step out: phi(0) = 0 and a decay of 1
        k = jnp.where(on[:, None, None], k.astype(jnp.float32), 0.0)
        vb = jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                              (s_n, hkv, dv, d))
        kernel = functools.partial(_retention_decode_kernel,
                                   step_rows=step_rows, group=group, hkv=hkv)
        per_head = lambda s, h, b, dec: (s, h, 0, 0)  # noqa: E731
        state_block = pl.BlockSpec(
            (None, None, None, step_rows, dv, d),
            lambda s, h, b, dec: (s, layer, h, b, 0, 0))
        norm_block = pl.BlockSpec((None, None, None, rows, d),
                                  lambda s, h, b, dec: (s, layer, h, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s_n, hkv, rows // step_rows),
            in_specs=[
                pl.BlockSpec((None, None, group, d), per_head),
                pl.BlockSpec((None, None, 1, d), per_head),
                pl.BlockSpec((None, None, dv, d), per_head),
                state_block, norm_block],
            out_specs=[
                state_block, norm_block,
                pl.BlockSpec((None, None, group, dv), per_head),
                pl.BlockSpec((None, None, group, d), per_head),
                pl.BlockSpec((None, None, 8, d), per_head)],
            scratch_shapes=[
                pltpu.VMEM((group, dv, d), jnp.float32),
                pltpu.VMEM((rows, -(-group // 8) * 8, d), jnp.float32),
                pltpu.VMEM((-(-rows // 8) * 8, d), jnp.float32)])
        state, norm, num, den, amax = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(state.shape, state.dtype),
                jax.ShapeDtypeStruct(norm.shape, norm.dtype),
                jax.ShapeDtypeStruct((s_n, hkv, group, dv), jnp.float32),
                jax.ShapeDtypeStruct((s_n, hkv, group, d), jnp.float32),
                jax.ShapeDtypeStruct((s_n, hkv, 8, d), jnp.float32)],
            # operands: the decays (prefetched), q, k, vb, state, norm
            input_output_aliases={4: 0, 5: 1},
            interpret=pallas_interpret(interpret),
            name="retention_decode",
        )(decay.reshape(-1), q.astype(jnp.float32).reshape(s_n, hkv, group, d),
          k.reshape(s_n, hkv, 1, d), vb, state, norm)
        den = jnp.sum(den, axis=-1)
        y = num / (den + eps)[..., None]
        absmax = jnp.max(amax * active.astype(jnp.float32)[:, None, None,
                                                           None])
        return (state, norm, y.reshape(s_n, hq, dv), den.reshape(s_n, hq),
                absmax)


def _retention_usable(state, norm, q, k, v, g, active, **kw):
    """The kernel takes float32 states whose rows are whole vectors of lanes
    and whose values fill sublanes; the tiny test models take the generic
    path."""
    if getattr(state, "ndim", 0) != 6 or getattr(q, "ndim", 0) != 3:
        return False
    return (state.dtype == jnp.float32 and q.shape[-1] % 128 == 0
            and v.shape[-1] % 8 == 0 and q.shape[1] % k.shape[1] == 0)


# ----------------------------------------------------------------- prefill


def power_retention_prefill_xla(q, k, v, g, valid, *, eps: float = 1e-6):
    """ONE prompt. q (T, Hq, d), k (T, Hkv, d), v (T, Hkv, dv), g (T, Hkv)
    the log of the decay, ``valid`` (T,) bool: the real positions (end
    padding), which alone enter the weights and the decay's sums. Returns
    ``(y (T, Hq, dv), state (Hkv, rows, dv, d), norm (Hkv, rows, d), den
    (T, Hq))``: every position's normalised read by the quadratic form, the
    state the last real position leaves (one product of ``phi(K)^T``,
    decayed to that position, with ``[V, 1]``) and the normalisers before
    ``eps``. All float32."""
    t, hq, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    group = hq // hkv
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    gate = jnp.where(valid[:, None], g.astype(jnp.float32), 0.0)
    total = jnp.cumsum(gate, axis=0)                            # (T, Hkv)
    with jax.named_scope("retention_prefill"):
        s = jnp.einsum("thgd,jhd->hgtj", q.reshape(t, hkv, group, d), k,
                       precision=_HIGHEST) / math.sqrt(d)
        pos = jnp.arange(t)
        seen = (pos[None, :] <= pos[:, None]) & valid[None, :]  # [t, j]
        fade = jnp.exp(jnp.where(seen[None], total.T[:, :, None]
                                 - total.T[:, None, :], -1e30))  # (Hkv, t, j)
        w = s * s * fade[:, None]
        den = jnp.sum(w, axis=-1)                               # (Hkv, g, T)
        num = jnp.einsum("hgtj,jhv->thgv", w, v, precision=_HIGHEST)
        y = num / (den.transpose(2, 0, 1) + eps)[..., None]
    with jax.named_scope("retention_state_build"):
        # padded positions add no decay, so the last row of the running sum
        # is the last real position's
        left = jnp.where(valid[:, None], jnp.exp(total[-1][None] - total),
                         0.0)
        pk = power_phi(k) * left[:, :, None, None]              # (T,Hkv,R,d)
        state = jnp.einsum("jhra,jhv->hrva", pk, v, precision=_HIGHEST)
        norm = jnp.sum(pk, axis=0)
    return (y.reshape(t, hq, dv), state, norm,
            den.transpose(2, 0, 1).reshape(t, hq))


# -------------------------------------------------------------- validation


def _check_power_retention():
    """Validation case: the recurrence (generic decode, then the kernel,
    interpreted) against the quadratic form in numpy, after a prefill of the
    first positions."""
    rs = np.random.RandomState(5)
    t, t0, hq, hkv, d, dv = 9, 5, 4, 2, 128, 8
    q = rs.randn(t, hq, d).astype(np.float32)
    k = rs.randn(t, hkv, d).astype(np.float32)
    v = rs.randn(t, hkv, dv).astype(np.float32)
    g = np.log(rs.uniform(0.8, 0.999, (t, hkv))).astype(np.float32)
    eps = 1e-6
    want = np.zeros((t, hq, dv), np.float32)
    for a in range(hq):
        c = a // (hq // hkv)
        for i in range(t):
            w = np.array([(q[i, a] @ k[j, c]) ** 2 / d
                          * np.exp(g[j + 1:i + 1, c].sum())
                          for j in range(i + 1)])
            want[i, a] = (w @ v[:i + 1, c]) / (w.sum() + eps)
    pad = 3
    padded = lambda x: jnp.asarray(np.concatenate(  # noqa: E731
        [x[:t0], np.ones((pad,) + x.shape[1:], x.dtype)]))
    y0, state, norm, _ = power_retention_prefill_xla(
        padded(q), padded(k), padded(v), padded(g),
        jnp.arange(t0 + pad) < t0, eps=eps)
    np.testing.assert_allclose(np.asarray(y0[:t0]), want[:t0], rtol=2e-4,
                               atol=2e-5)
    for fn in (power_retention_decode_xla,
               functools.partial(power_retention_decode_pallas,
                                 interpret=True)):
        # two slots, two layers: the live state in slot 1, layer 1
        pool = jnp.zeros((2, 2) + state.shape).at[1, 1].set(state)
        pool_n = jnp.zeros((2, 2) + norm.shape).at[1, 1].set(norm)
        on = jnp.asarray([0, 1])
        for i in range(t0, t):
            two = lambda x: jnp.asarray(np.stack([x[i], x[i]]))  # noqa: E731
            pool, pool_n, y, _, _ = fn(pool, pool_n, two(q), two(k), two(v),
                                       two(g), on, layer=1, eps=eps)
            np.testing.assert_allclose(np.asarray(y[1]), want[i], rtol=2e-4,
                                       atol=2e-5)
        assert float(jnp.max(jnp.abs(pool[0]))) == 0.0   # sat every step out


def register_platform_retention() -> None:
    """Register ``power_retention_decode`` (generic body, Pallas kernel as
    the TPU helper) and ``power_retention_prefill`` (generic only)."""
    from deeplearning4j_tpu.ops.registry import registry
    from deeplearning4j_tpu.ops import validation as _validation

    reg = registry()
    if "power_retention_decode" not in reg:
        reg.register(
            "power_retention_decode", power_retention_decode_xla,
            doc="one decode token a slot of degree-2 power retention over the "
                "pool of states, updated in place (state:[S,L,Hkv,R,dv,d], "
                "norm:[S,L,Hkv,R,d], q:[S,Hq,d], k:[S,Hkv,d], v:[S,Hkv,dv], "
                "g:[S,Hkv], active:[S], layer=, eps= -> state, norm, "
                "y:[S,Hq,dv], den:[S,Hq], absmax)")
        reg.register_platform("power_retention_decode", "tpu",
                              power_retention_decode_pallas,
                              _retention_usable)
        reg.register(
            "power_retention_prefill", power_retention_prefill_xla,
            doc="one prompt of degree-2 power retention: the quadratic form "
                "and the state it leaves (q:[T,Hq,d], k:[T,Hkv,d], "
                "v:[T,Hkv,dv], g:[T,Hkv], valid:[T], eps= -> y:[T,Hq,dv], "
                "state:[Hkv,R,dv,d], norm:[Hkv,R,d], den:[T,Hq])")
        _validation.add_case("power_retention_decode", _check_power_retention)
        _validation.add_case("power_retention_prefill",
                             _check_power_retention)
