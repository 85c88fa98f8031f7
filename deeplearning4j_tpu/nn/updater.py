"""Updaters (optimizer math) + learning-rate schedules.

Reference parity:
  * ND4J ``GradientUpdater`` impls (org/nd4j/linalg/learning/ — AdamUpdater,
    NesterovsUpdater, RmsPropUpdater, …) and their config twins
    (org/nd4j/linalg/learning/config/Adam.java etc.): stateful in-place
    view-buffer updates over the flattened gradient.
  * ISchedule impls (org/nd4j/linalg/schedule/ — StepSchedule,
    ExponentialSchedule, InverseSchedule, PolySchedule, SigmoidSchedule,
    MapSchedule, CycleSchedule).

TPU-native realization: each updater is a pure function
``(grad, state, lr, step) -> (update, new_state)`` applied leaf-wise over the
param pytree inside the single compiled train step (the reference's separate
updater pass fuses away). The update MATH matches the reference exactly so
parity tests can compare trajectories; optax exists in-env but we keep our own
transparent impls for exact-parity control, exposing ``as_optax()`` adapters.

State is a dict of pytrees (like the reference's single flat
``updaterStateViewArray`` carved into per-updater views — here a pytree keeps
the same exact-resume capability, see serde.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Schedules (ISchedule analog). All are pure fns of (initial leaning rate
# params..., iteration, epoch) evaluated inside jit — step is a traced scalar.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Base schedule: fixed value (the no-schedule default)."""

    value: float = 1e-3

    def __call__(self, iteration, epoch=None):
        return jnp.asarray(self.value, jnp.float32)

    # -- JSON round trip (Jackson-polymorphic analog) -----------------------
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["@type"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> Optional["Schedule"]:
        if d is None:
            return None
        d = dict(d)
        cls = _SCHEDULES[d.pop("@type")]
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class StepSchedule(Schedule):
    """value * decay^floor(iter / step) — reference StepSchedule.java."""

    decay_rate: float = 0.1
    step: float = 1000.0

    def __call__(self, iteration, epoch=None):
        it = jnp.asarray(iteration, jnp.float32)
        return self.value * self.decay_rate ** jnp.floor(it / self.step)


@dataclasses.dataclass(frozen=True)
class ExponentialSchedule(Schedule):
    """value * gamma^iter — reference ExponentialSchedule.java."""

    gamma: float = 0.99

    def __call__(self, iteration, epoch=None):
        return self.value * self.gamma ** jnp.asarray(iteration, jnp.float32)


@dataclasses.dataclass(frozen=True)
class InverseSchedule(Schedule):
    """value / (1 + gamma*iter)^power — reference InverseSchedule.java."""

    gamma: float = 0.01
    power: float = 1.0

    def __call__(self, iteration, epoch=None):
        it = jnp.asarray(iteration, jnp.float32)
        return self.value / (1.0 + self.gamma * it) ** self.power


@dataclasses.dataclass(frozen=True)
class PolySchedule(Schedule):
    """value * (1 - iter/maxIter)^power — reference PolySchedule.java."""

    power: float = 1.0
    max_iter: int = 10000

    def __call__(self, iteration, epoch=None):
        it = jnp.asarray(iteration, jnp.float32)
        frac = jnp.clip(it / float(self.max_iter), 0.0, 1.0)
        return self.value * (1.0 - frac) ** self.power


@dataclasses.dataclass(frozen=True)
class SigmoidSchedule(Schedule):
    """value / (1 + exp(-gamma*(iter-stepSize))) — reference SigmoidSchedule."""

    gamma: float = 0.01
    step_size: int = 1000

    def __call__(self, iteration, epoch=None):
        it = jnp.asarray(iteration, jnp.float32)
        return self.value / (1.0 + jnp.exp(-self.gamma * (it - self.step_size)))


@dataclasses.dataclass(frozen=True)
class CycleSchedule(Schedule):
    """1cycle policy (reference CycleSchedule.java): ramp up then anneal."""

    initial_lr: float = 1e-4
    max_lr: float = 1e-2
    cycle_length: int = 1000
    annealing_length: int = 100
    annealing_decay: float = 0.1

    def __call__(self, iteration, epoch=None):
        it = jnp.asarray(iteration, jnp.float32)
        pos = jnp.mod(it, float(self.cycle_length))
        up = float(self.cycle_length - self.annealing_length) / 2.0
        lr_up = self.initial_lr + (self.max_lr - self.initial_lr) * (pos / up)
        lr_down = self.max_lr - (self.max_lr - self.initial_lr) * ((pos - up) / up)
        ann_pos = (pos - (self.cycle_length - self.annealing_length)) / float(
            self.annealing_length
        )
        lr_ann = self.initial_lr * (
            1.0 + ann_pos * (self.annealing_decay - 1.0)
        )
        lr = jnp.where(pos < up, lr_up, jnp.where(pos < 2 * up, lr_down, lr_ann))
        return lr


@dataclasses.dataclass(frozen=True)
class MapSchedule(Schedule):
    """Piecewise-constant from an {iteration: lr} map — reference MapSchedule."""

    values: Tuple[Tuple[int, float], ...] = ()

    def __call__(self, iteration, epoch=None):
        it = jnp.asarray(iteration, jnp.float32)
        pts = sorted(self.values)
        lr = jnp.asarray(self.value, jnp.float32)
        for start, v in pts:
            lr = jnp.where(it >= start, v, lr)
        return lr

    def to_dict(self) -> Dict[str, Any]:
        return {
            "@type": "MapSchedule",
            "value": self.value,
            "values": [list(p) for p in self.values],
        }

    @staticmethod
    def _from(value, values):
        return MapSchedule(value=value, values=tuple((int(a), float(b)) for a, b in values))


_SCHEDULES = {
    c.__name__: c
    for c in [
        Schedule,
        StepSchedule,
        ExponentialSchedule,
        InverseSchedule,
        PolySchedule,
        SigmoidSchedule,
        CycleSchedule,
    ]
}
_SCHEDULES["MapSchedule"] = MapSchedule._from  # type: ignore[assignment]


# ---------------------------------------------------------------------------
# Updaters (GradientUpdater analog). Pure leaf-wise transforms.
# ---------------------------------------------------------------------------


def _fused_updater_enabled() -> bool:
    """``DL4J_TPU_FUSED_UPDATER`` opt-out, read at trace time (train steps
    re-read it only on recompile — same contract as the fusion passes)."""
    import os

    v = os.environ.get("DL4J_TPU_FUSED_UPDATER", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


@dataclasses.dataclass(frozen=True)
class Updater:
    """Base updater config. Subclasses define the exact reference math.

    ``learning_rate`` may be a float or a Schedule. ``init_state`` /
    ``apply`` operate on a single leaf; MultiLayerUpdater maps them over the
    param pytree (the reference's per-param UpdaterBlock decomposition).
    """

    learning_rate: Any = 1e-3

    def lr(self, iteration, epoch=None):
        if isinstance(self.learning_rate, Schedule):
            return self.learning_rate(iteration, epoch)
        return jnp.asarray(self.learning_rate, jnp.float32)

    # state: dict name -> array shaped like the param leaf
    def init_state(self, param) -> Dict[str, jax.Array]:
        return {}

    def scalars(self, lr, step) -> Tuple[jax.Array, ...]:
        """The per-step scalars ``apply_leaf`` needs — the scheduled lr and
        whatever depends on ``step`` alone (Adam-family bias corrections).
        Computed once per step OUTSIDE the elementwise math, so the fused
        Pallas kernel (ops/pallas_updater.py) receives them as inputs:
        Mosaic has no ``powf`` and cannot trace ``beta**t`` in its body."""
        return (lr,)

    def apply_leaf(self, grad, state, *scalars):
        """The elementwise updater math for one leaf, given
        :meth:`scalars`. Return (update, new_state)."""
        raise NotImplementedError

    def apply(self, grad, state, lr, step):
        """Return (update, new_state); params -= update downstream."""
        return self.apply_leaf(grad, state, *self.scalars(lr, step))

    # -- fused step (ops/pallas_updater.py) ---------------------------------
    def _fusable(self) -> bool:
        """Only the exact catalog classes route through the registry op: a
        user subclass overriding ``apply`` must keep its override."""
        return UPDATERS.get(type(self).__name__) is type(self)

    def fused_hyper(self) -> Dict[str, float]:
        """Constructor fields as static kwargs for the fused registry op
        (``learning_rate`` excluded — the scheduled lr rides as a traced
        scalar)."""
        return {f.name: float(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if f.name != "learning_rate"}

    def apply_fused(self, param, grad, state, lr, step):
        """One fused optimizer step: ``(new_param, new_state)``.

        Routes through the ``fused_updater_step`` registry op so the TPU
        platform helper (one Pallas kernel reading param/grad/state once)
        can take the leaf when the tuning table says it wins; the generic
        impl calls this class's own ``apply``, so trajectories are
        bit-identical to the unfused path everywhere. Opt-out:
        ``DL4J_TPU_FUSED_UPDATER=0`` (falls back to ``apply`` inline)."""
        if _fused_updater_enabled() and self._fusable():
            from deeplearning4j_tpu.ops.registry import registry

            keys = sorted(state)
            out = registry().get("fused_updater_step")(
                param, grad, lr, step, *(state[k] for k in keys),
                kind=type(self).__name__, **self.fused_hyper())
            return out[0], dict(zip(keys, out[1:]))
        u, new_state = self.apply(grad, state, lr, step)
        return param - u, new_state

    def to_dict(self) -> Dict[str, Any]:
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, Schedule):
                v = {"__schedule__": v.to_dict()}
            d[f.name] = v
        d["@type"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Updater":
        d = dict(d)
        cls = UPDATERS[d.pop("@type")]
        for k, v in list(d.items()):
            if isinstance(v, dict) and "__schedule__" in v:
                d[k] = Schedule.from_dict(v["__schedule__"])
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class Sgd(Updater):
    """SgdUpdater: update = lr * g."""

    learning_rate: Any = 1e-1

    def apply_leaf(self, grad, state, lr):
        return lr * grad, state


@dataclasses.dataclass(frozen=True)
class NoOp(Updater):
    """NoOpUpdater: passes the raw gradient through (update = g)."""

    def apply_leaf(self, grad, state, lr):
        return grad, state


@dataclasses.dataclass(frozen=True)
class Frozen(Updater):
    """The FrozenLayer effect at the updater level: update is exactly zero,
    so the layer's params never move (reference FrozenLayer zeroes the
    gradient in backprop; here the layer stays in the fused step but its
    update is dropped)."""

    learning_rate: Any = 0.0

    def apply_leaf(self, grad, state, lr):
        return jnp.zeros_like(grad), state


@dataclasses.dataclass(frozen=True)
class Nesterovs(Updater):
    """NesterovsUpdater (Nesterov momentum).

    Reference math (NesterovsUpdater.java): vPrev = v; v = mu*v - lr*g;
    params += mu*vPrev - (1+mu)*v. We return `update` s.t. params -= update.
    """

    learning_rate: Any = 1e-1
    momentum: float = 0.9

    def init_state(self, param):
        return {"v": jnp.zeros_like(param)}

    def apply_leaf(self, grad, state, lr):
        mu = self.momentum
        v_prev = state["v"]
        v = mu * v_prev - lr * grad
        # Sutskever form: params += (1+mu)*v - mu*vPrev; with our
        # params -= update convention, update = mu*vPrev - (1+mu)*v.
        update = mu * v_prev - (1 + mu) * v
        return update, {"v": v}


@dataclasses.dataclass(frozen=True)
class AdaGrad(Updater):
    """AdaGradUpdater: h += g²; update = lr * g / (sqrt(h) + eps)."""

    learning_rate: Any = 1e-1
    epsilon: float = 1e-6

    def init_state(self, param):
        return {"h": jnp.full_like(param, self.epsilon)}

    def apply_leaf(self, grad, state, lr):
        h = state["h"] + grad * grad
        update = lr * grad / (jnp.sqrt(h) + self.epsilon)
        return update, {"h": h}


@dataclasses.dataclass(frozen=True)
class RmsProp(Updater):
    """RmsPropUpdater: g2 = d*g2 + (1-d)*g²; update = lr*g/sqrt(g2+eps)."""

    learning_rate: Any = 1e-1
    rms_decay: float = 0.95
    epsilon: float = 1e-8

    def init_state(self, param):
        return {"g2": jnp.full_like(param, self.epsilon)}

    def apply_leaf(self, grad, state, lr):
        g2 = self.rms_decay * state["g2"] + (1 - self.rms_decay) * grad * grad
        update = grad * lr / jnp.sqrt(g2 + self.epsilon)
        return update, {"g2": g2}


@dataclasses.dataclass(frozen=True)
class AdaDelta(Updater):
    """AdaDeltaUpdater: rho-averaged g² and Δ² ratio; lr-free."""

    rho: float = 0.95
    epsilon: float = 1e-6

    def init_state(self, param):
        return {"msg": jnp.zeros_like(param), "msdx": jnp.zeros_like(param)}

    def apply_leaf(self, grad, state, lr):
        msg = self.rho * state["msg"] + (1 - self.rho) * grad * grad
        dx = (
            jnp.sqrt(state["msdx"] + self.epsilon)
            / jnp.sqrt(msg + self.epsilon)
        ) * grad
        msdx = self.rho * state["msdx"] + (1 - self.rho) * dx * dx
        return dx, {"msg": msg, "msdx": msdx}


def _adam_alpha(upd, lr, step):
    """Bias-corrected step size shared by Adam and AmsGrad:
    ``lr * sqrt(1-b2^t)/(1-b1^t)``."""
    t = jnp.asarray(step, jnp.float32) + 1.0
    return lr * jnp.sqrt(1 - upd.beta2**t) / (1 - upd.beta1**t)


@dataclasses.dataclass(frozen=True)
class Adam(Updater):
    """AdamUpdater — exact reference math incl. bias correction.

    m = b1*m + (1-b1)*g ; v = b2*v + (1-b2)*g²
    alpha_t = lr * sqrt(1-b2^t)/(1-b1^t) ; update = alpha_t * m / (sqrt(v)+eps)
    """

    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, param):
        return {"m": jnp.zeros_like(param), "v": jnp.zeros_like(param)}

    def scalars(self, lr, step):
        return (_adam_alpha(self, lr, step),)

    def apply_leaf(self, grad, state, alpha):
        m = self.beta1 * state["m"] + (1 - self.beta1) * grad
        v = self.beta2 * state["v"] + (1 - self.beta2) * grad * grad
        update = alpha * m / (jnp.sqrt(v) + self.epsilon)
        return update, {"m": m, "v": v}


@dataclasses.dataclass(frozen=True)
class AdaMax(Updater):
    """AdaMaxUpdater: v = max(b2*v, |g|); update = lr/(1-b1^t) * m/v."""

    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, param):
        return {"m": jnp.zeros_like(param), "u": jnp.zeros_like(param)}

    def scalars(self, lr, step):
        t = jnp.asarray(step, jnp.float32) + 1.0
        return (lr / (1 - self.beta1**t),)

    def apply_leaf(self, grad, state, lr_hat):
        m = self.beta1 * state["m"] + (1 - self.beta1) * grad
        u = jnp.maximum(self.beta2 * state["u"], jnp.abs(grad))
        update = lr_hat * m / (u + self.epsilon)
        return update, {"m": m, "u": u}


@dataclasses.dataclass(frozen=True)
class Nadam(Updater):
    """NadamUpdater: Nesterov-accelerated Adam (reference math)."""

    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, param):
        return {"m": jnp.zeros_like(param), "v": jnp.zeros_like(param)}

    def scalars(self, lr, step):
        t = jnp.asarray(step, jnp.float32) + 1.0
        return (lr, 1 - self.beta1**t, 1 - self.beta2**t)

    def apply_leaf(self, grad, state, lr, c1, c2):
        m = self.beta1 * state["m"] + (1 - self.beta1) * grad
        v = self.beta2 * state["v"] + (1 - self.beta2) * grad * grad
        m_hat = m / c1
        v_hat = v / c2
        update = (
            lr
            * (self.beta1 * m_hat + (1 - self.beta1) * grad / c1)
            / (jnp.sqrt(v_hat) + self.epsilon)
        )
        return update, {"m": m, "v": v}


@dataclasses.dataclass(frozen=True)
class AmsGrad(Updater):
    """AMSGradUpdater: Adam with max-tracked second moment."""

    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def init_state(self, param):
        return {
            "m": jnp.zeros_like(param),
            "v": jnp.zeros_like(param),
            "vhat": jnp.zeros_like(param),
        }

    def scalars(self, lr, step):
        return (_adam_alpha(self, lr, step),)

    def apply_leaf(self, grad, state, alpha):
        m = self.beta1 * state["m"] + (1 - self.beta1) * grad
        v = self.beta2 * state["v"] + (1 - self.beta2) * grad * grad
        vhat = jnp.maximum(state["vhat"], v)
        update = alpha * m / (jnp.sqrt(vhat) + self.epsilon)
        return update, {"m": m, "v": v, "vhat": vhat}


UPDATERS = {
    c.__name__: c
    for c in [Sgd, NoOp, Frozen, Nesterovs, AdaGrad, RmsProp, AdaDelta, Adam,
              AdaMax, Nadam, AmsGrad]
}


def get_updater(spec) -> Updater:
    """Resolve an updater from an Updater, name, or dict."""
    if isinstance(spec, Updater):
        return spec
    if isinstance(spec, str):
        return UPDATERS[spec]()
    if isinstance(spec, dict):
        return Updater.from_dict(spec)
    raise TypeError(f"cannot resolve updater from {spec!r}")


def as_optax(updater: Updater):
    """Adapter: wrap an Updater as an optax.GradientTransformation."""
    import optax

    def init_fn(params):
        return {
            "state": jax.tree.map(updater.init_state, params),
            "step": jnp.zeros((), jnp.int32),
        }

    def update_fn(grads, opt_state, params=None):
        step = opt_state["step"]
        lr = updater.lr(step)
        flat_g, treedef = jax.tree.flatten(grads)
        flat_s = treedef.flatten_up_to(opt_state["state"])
        ups, news = [], []
        for g, s in zip(flat_g, flat_s):
            u, ns = updater.apply(g, s, lr, step)
            ups.append(-u)
            news.append(ns)
        return treedef.unflatten(ups), {
            "state": treedef.unflatten(news),
            "step": step + 1,
        }

    return optax.GradientTransformation(init_fn, update_fn)
