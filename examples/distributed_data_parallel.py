"""Data-parallel training over a device mesh — the ParallelWrapper /
SharedTrainingMaster role, the TPU way: shard the batch over a mesh axis
and let XLA insert the gradient all-reduce over ICI.

Run: python examples/distributed_data_parallel.py
(forces an 8-device virtual CPU mesh so it runs anywhere; on a real pod,
drop the env lines and the same code spans the chips)"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

import numpy as np  # noqa: E402

from deeplearning4j_tpu import nn  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh  # noqa: E402


def main():
    conf = (nn.builder()
            .seed(7)
            .updater(nn.Nesterovs(learning_rate=0.05, momentum=0.9))
            .list()
            .layer(nn.DenseLayer(n_out=64, activation="relu"))
            .layer(nn.OutputLayer(n_out=10, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.feed_forward(32))
            .build())
    net = nn.MultiLayerNetwork(conf).init()

    mesh = make_mesh({"data": len(jax.devices())})
    pw = ParallelWrapper(net, mesh=mesh)
    r = np.random.RandomState(0)
    x = r.randn(512, 32).astype(np.float32)
    y = np.eye(10)[r.randint(0, 10, 512)].astype(np.float32)
    pw.fit(DataSet(x, y), epochs=3, batch_size=256)
    print(f"trained over {len(jax.devices())} devices; "
          f"final score {float(net.score()):.4f}")


if __name__ == "__main__":
    main()
