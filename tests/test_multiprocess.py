"""Multi-process distributed tests — SURVEY §5.5's translation: two real OS
processes form a jax.distributed cluster over loopback (the Spark-local /
Aeron-loopback pattern), validating the multi-host bootstrap + global-mesh
collectives the pod path relies on."""

import os
import socket
import subprocess
import sys

import pytest

WORKER = r"""
import os, sys
proc_id = int(sys.argv[1]); nprocs = int(sys.argv[2]); port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from deeplearning4j_tpu.parallel import initialize_distributed, host_shard
initialize_distributed(coordinator_address=f"127.0.0.1:{port}",
                       num_processes=nprocs, process_id=proc_id)
assert jax.process_count() == nprocs, jax.process_count()
assert len(jax.devices()) == 4 * nprocs, len(jax.devices())

# global-mesh collective: psum over all devices of both processes
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))

from jax.experimental.shard_map import shard_map
def allreduce_ones(x):
    return jax.lax.psum(x, "data")
fn = shard_map(allreduce_ones, mesh=mesh, in_specs=P("data"), out_specs=P())

# each process supplies ITS shard of the global array
local = jnp.ones((4, 2))  # 4 local devices x 1 row
from jax import make_array_from_single_device_arrays
global_shape = (4 * nprocs, 2)
sharding = NamedSharding(mesh, P("data"))
arrs = [jax.device_put(local[i:i+1], d)
        for i, d in enumerate(jax.local_devices())]
garr = make_array_from_single_device_arrays(global_shape, sharding, arrs)
out = fn(garr)
total = float(jax.device_get(out.addressable_data(0))[0, 0])
assert total == 4 * nprocs, total

# host_shard partitions deterministically
shard = host_shard(list(range(10)))
assert shard == list(range(10))[proc_id::nprocs]
print(f"WORKER_{proc_id}_OK")
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# (no pytest-timeout in env — the inner communicate(timeout=150) bounds the run)
def test_two_process_cluster():
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", WORKER, str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env, text=True)
        for i in range(2)
    ]
    outs = []
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multiprocess worker timed out")
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"WORKER_{i}_OK" in out


import numpy as np  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestDistributedWord2Vec:
    def test_two_process_averaging_matches_vocab_and_trains(self, tmp_path):
        """SparkWord2Vec role: 2-rank corpus-sharded training with
        parameter averaging; rank 0 saves the vectors, and similarity
        structure from the toy corpus must hold (cats cluster together)."""
        worker = tmp_path / "w2v_worker.py"
        worker.write_text("""
import jax
import sys, numpy as np
sys.path.insert(0, %r)
from deeplearning4j_tpu.parallel.launch import initialize_distributed
initialize_distributed()
from deeplearning4j_tpu.nlp.word2vec import Word2Vec, distributed_word2vec_fit
corpus = ([["cat", "purrs", "softly"], ["cat", "meows", "softly"],
           ["dog", "barks", "loudly"], ["dog", "growls", "loudly"]] * 40)
w2v = Word2Vec(layer_size=16, window_size=2, negative_samples=3,
               learning_rate=0.05, epochs=1, seed=3)
losses = distributed_word2vec_fit(w2v, corpus, epochs=8)
assert losses and np.isfinite(losses[-1])
if jax.process_index() == 0:
    sim_same = w2v.similarity("cat", "meows")
    sim_diff = w2v.similarity("cat", "barks")
    assert sim_same > sim_diff, (sim_same, sim_diff)
    np.save(%r, np.asarray(w2v.syn0))
""" % (REPO_ROOT, str(tmp_path / "syn0.npy")))
        from deeplearning4j_tpu.parallel.launch import launch
        rc = launch(2, [str(worker)], timeout=300.0)
        assert rc == 0
        syn0 = np.load(tmp_path / "syn0.npy")
        assert syn0.shape[1] == 16 and np.isfinite(syn0).all()
