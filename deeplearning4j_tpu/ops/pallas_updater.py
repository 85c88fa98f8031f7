"""Fused updater step — one elementwise kernel for the whole optimizer math.

The reference applies updaters as a separate pass over the flattened
gradient view (``BaseMultiLayerUpdater.update``); our train steps apply the
same math leaf-wise with jnp ops, which XLA usually fuses — but each leaf's
chain still reads param/grad/state from HBM and writes param/state back as
separate fusions, and under bf16 policies XLA splits the chain at dtype
boundaries. ``fused_updater_step`` makes the one-HBM-pass contract explicit:

    new_param, *new_state = fused_updater_step(param, grad, lr, step,
                                               *state, kind="Adam", ...)

* the **generic impl** runs the exact ``nn/updater.py`` math (it calls the
  same ``Updater.apply``), so trajectories are bit-identical to the unfused
  step everywhere — the op is safe on the default train path.
* the **Pallas TPU helper** flattens the leaf to (rows, 128) lanes and runs
  the identical ``apply_leaf`` math inside one kernel: param, grad and
  every state buffer are read once, new param + state written once. All 11
  updater kinds (Sgd…AmsGrad) share this one kernel — the per-kind math is
  traced into the kernel body from the same dataclasses; the per-step
  scalars (``Updater.scalars``: lr, Adam-family bias corrections) are
  computed in the wrapper and ride in through SMEM.
* dispatch consults the tuning table (``fused_updater_step.min_size``):
  below the measured crossover the generic XLA chain wins (kernel launch
  overhead), above it the fused kernel does — ``ops/tuning.py``.

``Updater.apply_fused`` (nn/updater.py) is the train-step entry: MLN/
ComputationGraph (``apply_layer_updates``) and the SameDiff training
session route through it, with ``DL4J_TPU_FUSED_UPDATER=0`` as the opt-out.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.registry import op, pallas_interpret

LANES = 128


@functools.lru_cache(maxsize=None)
def _updater_and_keys(kind: str, hyper_items: Tuple[Tuple[str, object], ...]):
    """Resolve (updater instance, canonical state-key order) for a static
    (kind, hyperparams) pair. Lazy import: nn.updater must not load during
    ops package init (layer modules import ops back)."""
    from deeplearning4j_tpu.nn.updater import UPDATERS

    if kind not in UPDATERS:
        raise ValueError(f"fused_updater_step: unknown updater kind '{kind}'"
                         f"; valid: {sorted(UPDATERS)}")
    upd = UPDATERS[kind](**dict(hyper_items))
    keys = tuple(sorted(upd.init_state(jnp.zeros((), jnp.float32))))
    return upd, keys


@op("fused_updater_step")
def fused_updater_step(param, grad, lr, step, *state, kind: str = "Sgd",
                       **hyper):
    """One optimizer step for one leaf: ``(new_param, *new_state)``.

    ``state`` rides positionally in SORTED-key order (Adam: m, v); ``kind``
    names an ``nn/updater.py`` updater class and ``hyper`` its constructor
    fields (``learning_rate`` excluded — ``lr`` is the already-scheduled
    traced scalar). The generic impl IS the reference math: it calls the
    same ``Updater.apply`` the unfused train step calls, then applies the
    ``params -= update`` convention."""
    upd, keys = _updater_and_keys(kind, tuple(sorted(hyper.items())))
    if len(state) != len(keys):
        raise ValueError(
            f"fused_updater_step[{kind}]: expected {len(keys)} state "
            f"arrays {list(keys)}, got {len(state)}")
    u, new = upd.apply(grad, dict(zip(keys, state)), lr, step)
    return (param - u,) + tuple(new[k] for k in keys)


# ---------------------------------------------------------------------------
# Pallas TPU helper
# ---------------------------------------------------------------------------


def _kernel(scal_ref, p_ref, g_ref, *refs, apply_leaf, keys, n_scalars):
    """One (block_rows, 128) tile: the full updater chain, traced from the
    same dataclass ``apply_leaf`` as the generic impl — the kernel cannot
    drift from the reference math because it IS the reference math. Tiles
    widen to f32 on load and stores cast back to the ref dtype: a no-op
    for f32 leaves (bit-identical to the generic impl), and for bf16
    leaves the only form a v5e accepts — its transcendental unit has no
    bf16 ops, so a bf16 ``sqrt`` in the body fails the TPU compiler."""
    n = len(keys)
    state_refs, out_refs = refs[:n], refs[n:]
    scalars = [scal_ref[i] for i in range(n_scalars)]
    f32 = jnp.float32
    st = {k: r[...].astype(f32) for k, r in zip(keys, state_refs)}
    u, new = apply_leaf(g_ref[...].astype(f32), st, *scalars)
    out_refs[0][...] = (p_ref[...].astype(f32) - u).astype(out_refs[0].dtype)
    for k, r in zip(keys, out_refs[1:]):
        r[...] = new[k].astype(r.dtype)


def _rows_for(size: int, block_rows: int) -> Tuple[int, int]:
    rows = -(-size // LANES)
    rows = -(-rows // block_rows) * block_rows
    return rows, rows * LANES


def fused_updater_helper(param, grad, lr, step, *state, kind: str = "Sgd",
                         block_rows: int = 0, interpret=None, **hyper):
    """Pallas forward for :func:`fused_updater_step` — same contract.

    The leaf is flattened and padded to (rows, 128) full-lane tiles (pad
    cells compute garbage that is sliced off; every updater's denominators
    carry an eps, so pads cannot NaN). One grid dimension walks row
    blocks; param/grad/state stream through VMEM once."""
    interpret = pallas_interpret(interpret)
    upd, keys = _updater_and_keys(kind, tuple(sorted(hyper.items())))
    if len(state) != len(keys):
        raise ValueError(
            f"fused_updater_step[{kind}]: expected {len(keys)} state "
            f"arrays {list(keys)}, got {len(state)}")
    if not block_rows:
        from deeplearning4j_tpu.ops import tuning

        block_rows = int(tuning.tuned("fused_updater_step", "block_rows",
                                      256))
    shape, size = param.shape, param.size
    rows, padded = _rows_for(size, block_rows)

    def to_tile(a):
        flat = a.reshape(-1)
        if padded != size:
            flat = jnp.pad(flat, (0, padded - size))
        return flat.reshape(rows, LANES)

    tiles = [to_tile(a) for a in (param, grad) + tuple(state)]
    scalars = jnp.stack([jnp.asarray(v, jnp.float32)
                         for v in upd.scalars(lr, step)])
    grid = (rows // block_rows,)
    tile_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    n_out = 1 + len(keys)
    outs = pl.pallas_call(
        functools.partial(_kernel, apply_leaf=upd.apply_leaf, keys=keys,
                          n_scalars=scalars.shape[0]),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), t.dtype)
                   for t in tiles[:1] + tiles[2:]],
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [tile_spec] * len(tiles),
        out_specs=[tile_spec] * n_out,
        interpret=interpret,
    )(scalars, *tiles)
    if n_out == 1:
        outs = [outs] if not isinstance(outs, (list, tuple)) else outs
    return tuple(o.reshape(-1)[:size].reshape(shape) for o in outs)


def _usable(param, grad, lr, step, *state, **kw):
    """PlatformHelper::isUsable: floating same-shape leaves, and a leaf
    large enough that one fused HBM pass beats the XLA chain (measured
    ``min_size`` crossover from the tuning table)."""
    shape = getattr(param, "shape", None)
    dt = getattr(param, "dtype", None)
    if shape is None or dt is None or not jnp.issubdtype(dt, jnp.floating):
        return False
    for a in (grad,) + state:
        if getattr(a, "shape", None) != shape:
            return False
    try:
        _, keys = _updater_and_keys(
            kw.get("kind", "Sgd"),
            tuple(sorted((k, v) for k, v in kw.items()
                         if k not in ("kind", "block_rows", "interpret"))))
    except (ValueError, TypeError):
        return False
    if len(state) != len(keys):
        return False
    from deeplearning4j_tpu.ops import tuning

    return param.size >= int(tuning.tuned("fused_updater_step", "min_size",
                                          65536))


def _check_fused_updater_step():
    """Validation case (ops.validation ratchet): generic vs the literal
    nn/updater.py math, and the Pallas kernel vs both, for a
    stateful kind (Adam) and a stateless one (Sgd)."""
    import numpy as np

    from deeplearning4j_tpu.nn.updater import Adam, Sgd

    r = np.random.RandomState(3)
    p = jnp.asarray(r.randn(37).astype(np.float32))  # ragged: exercises pad
    g = jnp.asarray(r.randn(37).astype(np.float32))
    lr, step = jnp.float32(1e-2), jnp.float32(4.0)

    adam = Adam(beta1=0.85)
    st = {"m": jnp.asarray(r.randn(37).astype(np.float32)),
          "v": jnp.asarray(np.abs(r.randn(37)).astype(np.float32))}
    u, new = adam.apply(g, st, lr, step)
    want = (np.asarray(p - u), np.asarray(new["m"]), np.asarray(new["v"]))
    got = fused_updater_step.fn(p, g, lr, step, st["m"], st["v"],
                                kind="Adam", beta1=0.85)
    got_pl = fused_updater_helper(p, g, lr, step, st["m"], st["v"],
                                  kind="Adam", beta1=0.85, block_rows=8)
    for w, a, b in zip(want, got, got_pl):
        np.testing.assert_allclose(np.asarray(a), w, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(b), w, rtol=1e-6, atol=1e-7)

    u, _ = Sgd(learning_rate=0.1).apply(g, {}, lr, step)
    got = fused_updater_step.fn(p, g, lr, step, kind="Sgd")
    got_pl = fused_updater_helper(p, g, lr, step, kind="Sgd", block_rows=8)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(p - u),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got_pl[0]), np.asarray(p - u),
                               rtol=1e-6, atol=1e-7)


def register_platform_fused_updater() -> None:
    """Install the Pallas kernel as the TPU platform override for
    fused_updater_step (cuDNN PlatformHelper pattern)."""
    from deeplearning4j_tpu.ops import validation as _validation
    from deeplearning4j_tpu.ops.registry import registry

    reg = registry()
    desc = reg.get("fused_updater_step")
    if "tpu" not in desc.platform_impls:
        reg.register_platform("fused_updater_step", "tpu",
                              fused_updater_helper, _usable)
        _validation.add_case("fused_updater_step", _check_fused_updater_step)
