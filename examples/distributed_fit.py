"""Multi-host data-parallel fit worker — run under the launcher:

    python -m deeplearning4j_tpu.parallel.launch --nprocs 2 --restarts 1 \
        -- examples/distributed_fit.py --steps 12 --checkpoint-dir /tmp/ck

Each process forms one rank of a jax.distributed cluster
(SharedTrainingMaster worker role), feeds ITS shard of every global batch,
and the jitted step's gradient all-reduce rides XLA collectives. Process 0
persists the replicated training state every --checkpoint-every steps; on
relaunch every rank restores the latest checkpoint and continues from the
NEXT step, which is what makes `launch --restarts N` an elastic
checkpoint-restart story (SURVEY §4.4, §6.3, §6.4).

--crash-at K + --crash-marker PATH inject a one-shot failure: rank 0 dies
hard at global step K on the first attempt only (the marker file makes the
relaunch skip the crash) — the fault-injection hook the recovery test uses.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

import numpy as np


def make_step_batch(step: int, global_batch: int, n_in: int, n_out: int):
    """Deterministic global batch for a step — every rank derives the SAME
    global data and slices its own contiguous shard."""
    r = np.random.RandomState(1000 + step)
    x = r.randn(global_batch, n_in).astype(np.float32)
    w_true = np.linspace(-1, 1, n_in * n_out).reshape(n_in, n_out)
    logits = x @ w_true
    y = (logits == logits.max(axis=1, keepdims=True)).astype(np.float32)
    return x, y


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--checkpoint-dir", required=True)
    ap.add_argument("--checkpoint-every", type=int, default=4)
    ap.add_argument("--out", default=None,
                    help="process 0 writes final losses+param digest here")
    ap.add_argument("--crash-at", type=int, default=0)
    ap.add_argument("--crash-marker", default=None)
    ns = ap.parse_args()

    import jax

    # cluster formation MUST precede any backend-initializing jax call, and
    # importing the framework creates RNG keys — so initialize first
    from deeplearning4j_tpu.parallel.launch import initialize_distributed

    initialize_distributed()

    from deeplearning4j_tpu import nn
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.parallel.checkpoint import TrainingCheckpointer
    from deeplearning4j_tpu.nn.listeners import TrainingListener
    pid, nproc = jax.process_index(), jax.process_count()
    n_in, n_out = 8, 4

    net = nn.MultiLayerNetwork(
        nn.builder().seed(7).updater(nn.Sgd(learning_rate=0.1)).list()
        .layer(nn.DenseLayer(n_out=16, activation="tanh"))
        .layer(nn.OutputLayer(n_out=n_out, activation="softmax", loss="mcxent"))
        .set_input_type(nn.InputType.feed_forward(n_in)).build()).init()

    ck = TrainingCheckpointer(ns.checkpoint_dir, use_orbax=False)
    restored = ck.restore(net)
    start = net.iteration_count if restored is not None else 0
    if restored is not None:
        print(f"[rank {pid}] resumed from step {start}", flush=True)

    local = ns.global_batch // nproc
    batches = []
    for step in range(start, ns.steps):
        x, y = make_step_batch(step, ns.global_batch, n_in, n_out)
        batches.append(DataSet(x[pid * local:(pid + 1) * local],
                               y[pid * local:(pid + 1) * local]))

    losses = []

    class Recorder(TrainingListener):
        def iteration_done(self, model, iteration, epoch, loss):
            losses.append(float(loss))
            if (ns.crash_at and iteration == ns.crash_at and pid == 0
                    and ns.crash_marker and not os.path.exists(ns.crash_marker)):
                open(ns.crash_marker, "w").write("crashed")
                print(f"[rank 0] injected crash at step {iteration}",
                      flush=True)
                os._exit(17)

    net.set_listeners(Recorder())
    pw = ParallelWrapper(net, mesh=make_mesh({"data": len(jax.devices())}))
    pw.fit(batches, epochs=1, checkpointer=ck,
           checkpoint_every=ns.checkpoint_every)

    if pid == 0 and ns.out:
        digest = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(net.params):
            digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        json.dump({"first_step": start, "losses": losses,
                   "param_sha256": digest.hexdigest(),
                   "final_iteration": net.iteration_count},
                  open(ns.out, "w"))
    print(f"[rank {pid}] done at step {net.iteration_count}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
