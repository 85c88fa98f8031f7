"""Round-5 verdict items 4+5: serving-grade ParallelInference (request
queue + dynamic batching window), distributed evaluation with cross-process
Evaluation merge, file-level ETL sharding, and the double-buffered
device-transfer path in ParallelWrapper.fit."""

import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import nn
from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.parallel.launch import ShardedDataSetIterator, distributed_evaluate
from deeplearning4j_tpu.parallel.mesh import ParallelInference, ParallelWrapper, make_mesh

from tests._helpers import _mln, _rng


def _small_net(d=12, classes=4):
    return _mln([
        nn.DenseLayer(n_out=32, activation="relu"),
        nn.OutputLayer(n_out=classes, activation="softmax", loss="mcxent"),
    ], nn.InputType.feed_forward(d))


class TestServingParallelInference:
    def test_predict_matches_output(self):
        net = _small_net()
        pi = ParallelInference(net, max_batch=8, window_ms=2.0).start()
        try:
            r = _rng(0)
            x = r.randn(5, 12).astype(np.float32)
            got = pi.predict(x)
            want = pi.output(x)
            np.testing.assert_allclose(got, want, atol=1e-5)
            # single-example request (no batch dim)
            one = pi.predict(x[0])
            np.testing.assert_allclose(one[0], want[0], atol=1e-5)
        finally:
            pi.stop()

    def test_concurrent_clients_get_their_own_rows(self):
        net = _small_net()
        pi = ParallelInference(net, max_batch=16, window_ms=5.0).start()
        try:
            r = _rng(1)
            xs = [r.randn(12).astype(np.float32) for _ in range(24)]
            direct = pi.output(np.stack(xs))
            results = [None] * len(xs)

            def client(i):
                results[i] = pi.predict(xs[i])[0]

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(xs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            for i, res in enumerate(results):
                assert res is not None, f"client {i} got no reply"
                np.testing.assert_allclose(res, direct[i], atol=1e-5)
        finally:
            pi.stop()

    def test_batching_beats_per_request(self):
        """The reference's dynamic-batching claim: many tiny concurrent
        requests through the batching window must beat one forward PER
        request by >=3x (each per-request call pays a full padded forward;
        the queue amortizes it)."""
        # a model big enough that one forward dominates threading overhead
        net = _mln([
            nn.DenseLayer(n_out=2048, activation="relu"),
            nn.DenseLayer(n_out=2048, activation="relu"),
            nn.OutputLayer(n_out=64, activation="softmax", loss="mcxent"),
        ], nn.InputType.feed_forward(512))
        pi = ParallelInference(net, max_batch=32, window_ms=20.0).start()
        try:
            r = _rng(2)
            xs = [r.randn(512).astype(np.float32) for _ in range(64)]
            pi.predict(xs[0])       # warm the compiled shape
            _ = pi.output(xs[0][None])

            t0 = time.perf_counter()
            for x in xs:
                _ = pi.output(x[None])  # per-request: one forward each
            per_request = time.perf_counter() - t0

            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=lambda x=x: pi.predict(x)) for x in xs]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            batched = time.perf_counter() - t0
            assert batched * 3 < per_request, (
                f"batched {batched:.3f}s vs per-request {per_request:.3f}s")
        finally:
            pi.stop()


class TestDistributedEvaluate:
    def test_single_process_passthrough(self):
        net = _small_net()
        r = _rng(3)
        x = r.randn(40, 12).astype(np.float32)
        y = np.eye(4)[r.randint(0, 4, 40)].astype(np.float32)
        it = ListDataSetIterator(DataSet(x, y), batch_size=10)
        ev = distributed_evaluate(net, it)
        it.reset()
        ev2 = net.evaluate(it)
        assert np.array_equal(ev.confusion, ev2.confusion)

    def test_two_process_merge_equals_single(self, tmp_path):
        """2-process jax.distributed run: each rank evaluates its shard of
        the same dataset; the merged Evaluation must equal a single-process
        evaluation over the full data (verdict item 4 'Done' gate)."""
        worker = tmp_path / "worker.py"
        worker.write_text(
            """
import jax
import sys, json, numpy as np
sys.path.insert(0, %r)
sys.path.insert(0, %r)
from deeplearning4j_tpu.parallel.launch import (
    initialize_distributed, ShardedDataSetIterator, distributed_evaluate)
initialize_distributed()
from deeplearning4j_tpu import nn
from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from tests._helpers import _mln, _rng
net = _mln([
    nn.DenseLayer(n_out=32, activation="relu"),
    nn.OutputLayer(n_out=4, activation="softmax", loss="mcxent"),
], nn.InputType.feed_forward(12))
r = _rng(3)
x = r.randn(40, 12).astype(np.float32)
y = np.eye(4)[r.randint(0, 4, 40)].astype(np.float32)
base = ListDataSetIterator(DataSet(x, y), batch_size=10)
ev = distributed_evaluate(net, ShardedDataSetIterator(base))
if jax.process_index() == 0:
    np.save(%r, ev.confusion)
""" % ("/root/repo", "/root/repo", str(tmp_path / "conf.npy")))
        from deeplearning4j_tpu.parallel.launch import launch
        rc = launch(2, [str(worker)], timeout=240.0)
        assert rc == 0
        merged = np.load(tmp_path / "conf.npy")

        net = _small_net()
        r = _rng(3)
        x = r.randn(40, 12).astype(np.float32)
        y = np.eye(4)[r.randint(0, 4, 40)].astype(np.float32)
        single = net.evaluate(ListDataSetIterator(DataSet(x, y),
                                                  batch_size=10))
        assert np.array_equal(merged, single.confusion)


class TestFileShardedETL:
    def _image_tree(self, tmp, n_per_class=6):
        from PIL import Image
        for lab in ("cat", "dog"):
            d = os.path.join(tmp, lab)
            os.makedirs(d, exist_ok=True)
            for i in range(n_per_class):
                arr = (np.random.RandomState(hash(lab) % 1000 + i)
                       .rand(8, 8, 3) * 255).astype(np.uint8)
                Image.fromarray(arr).save(os.path.join(d, f"{i}.png"))

    def test_shard_files_partitions_work(self, tmp_path):
        self._image_tree(str(tmp_path))
        from deeplearning4j_tpu.datasets.image import ImageRecordReader
        r0 = ImageRecordReader(str(tmp_path), 8, 8, batch_size=4)
        total = len(r0.files)
        r0.shard_files(0, 2)
        r1 = ImageRecordReader(str(tmp_path), 8, 8, batch_size=4)
        r1.shard_files(1, 2)
        assert len(r0.files) + len(r1.files) == total
        assert not (set(f for f, _ in r0.files)
                    & set(f for f, _ in r1.files))

    def test_sharded_iterator_uses_file_sharding(self, tmp_path):
        self._image_tree(str(tmp_path))
        from deeplearning4j_tpu.datasets.image import ImageRecordReader
        reader = ImageRecordReader(str(tmp_path), 8, 8, batch_size=4)
        total = len(reader.files)
        it = ShardedDataSetIterator(reader, process_id=1, num_processes=3)
        assert it._file_sharded
        assert len(reader.files) == len(list(range(total))[1::3])
        seen = sum(ds.num_examples() for ds in it)
        assert seen == len(reader.files)

    def test_round_robin_fallback_warns(self):
        r = _rng(4)
        x = r.randn(12, 4).astype(np.float32)
        y = np.eye(2)[r.randint(0, 2, 12)].astype(np.float32)
        base = ListDataSetIterator(DataSet(x, y), batch_size=4)
        with pytest.warns(UserWarning, match="full ETL"):
            it = ShardedDataSetIterator(base, process_id=0, num_processes=2)
        assert not it._file_sharded
        assert len(list(it)) == 2  # batches 0 and 2 of 3


class TestDoubleBufferedFit:
    def test_fit_correctness_unchanged(self):
        # the lookahead placement must not change results vs plain fit
        net_a = _small_net()
        net_b = _small_net()
        net_b.params = jax.tree.map(jnp.array, net_a.params)
        net_b.opt_state = jax.tree.map(jnp.array, net_a.opt_state)
        r = _rng(5)
        x = r.randn(32, 12).astype(np.float32)
        y = np.eye(4)[r.randint(0, 4, 32)].astype(np.float32)
        it = ListDataSetIterator(DataSet(x, y), batch_size=8)
        pw = ParallelWrapper(net_b, mesh=make_mesh({"data": 2}, devices=jax.devices()[:2]))
        pw.fit(it, epochs=2)
        it.reset()
        for _ in range(2):
            for ds in it:
                net_a.fit(ds.features, ds.labels)
            it.reset()
        da = jax.tree.map(lambda p, q: float(jnp.max(jnp.abs(p - q))),
                          net_a.params, net_b.params)
        assert jax.tree.reduce(max, da) < 2e-4
