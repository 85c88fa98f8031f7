"""Round-5 product-surface demo: an MoE network trained dp×ep through the
standard ParallelWrapper.fit(), and a config-built pipeline-parallel
trainer with the stock updaters/listeners — no hand-written shard_map.

Run: python examples/moe_pipeline_parallel.py
(forces an 8-device virtual CPU mesh so it runs anywhere; on a real pod
the same code spans the chips)"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from deeplearning4j_tpu import nn  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.listeners import ScoreIterationListener  # noqa: E402
from deeplearning4j_tpu.parallel.mesh import (  # noqa: E402
    ParallelWrapper, moe_ep_rules)
from deeplearning4j_tpu.parallel.pipeline import (  # noqa: E402
    PipelineParallelTrainer)


def moe_dp_ep():
    """A Mixture-of-Experts FFN declared like any other layer; the mesh's
    'expert' axis + moe_ep_rules shard the experts, GSPMD inserts the
    dispatch collectives."""
    b = (nn.builder().seed(0).updater(nn.Adam(learning_rate=5e-3)).list()
         .layer(nn.DenseLayer(n_out=32, activation="relu"))
         .layer(nn.MoELayer(d_hidden=64, n_experts=4, top_k=2,
                            activation="relu"))
         .layer(nn.OutputLayer(n_out=5, activation="softmax", loss="mcxent")))
    net = nn.MultiLayerNetwork(
        b.set_input_type(nn.InputType.feed_forward(32)).build()).init()

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "expert"))
    pw = ParallelWrapper(net, mesh=mesh, tp_rules=moe_ep_rules("expert"))
    r = np.random.RandomState(0)
    x = r.randn(256, 32).astype(np.float32)
    y = np.eye(5)[r.randint(0, 5, 256)].astype(np.float32)
    net.listeners = [ScoreIterationListener(5)]
    pw.fit(DataSet(x, y), epochs=6, batch_size=64)
    print(f"MoE dp×ep: final score {net.score():.4f}, "
          f"dropped assignments {float(net.net_state[1]['_dropped_frac']):.1%}")


def pipeline_dp_pp():
    """A transformer-ish block declared as layer configs, trained GPipe-
    style over a data×pipe mesh with Adam + listeners + the standard
    checkpointing hooks."""
    d = 16
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "pipe"))
    r = np.random.RandomState(1)
    head = {"W": jnp.asarray(r.randn(d, 3).astype(np.float32) * 0.3)}

    def head_fn(hp, feats, y):
        logp = jax.nn.log_softmax(feats @ hp["W"])
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    trainer = PipelineParallelTrainer.from_confs(
        [nn.DenseLayer(n_out=d, activation="tanh")],
        head_fn, d, mesh, num_microbatches=4,
        updater=nn.Adam(learning_rate=0.01), head_params=head)
    x = jnp.asarray(r.randn(32, d).astype(np.float32))
    y = jnp.asarray(np.eye(3)[r.randint(0, 3, 32)].astype(np.float32))
    losses = trainer.fit(x, y, steps=40)
    print(f"pipeline dp×pp: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {len(losses)} steps on mesh {dict(mesh.shape)}")


if __name__ == "__main__":
    moe_dp_ep()
    pipeline_dp_pp()
