"""Multi-host bootstrap + per-host data sharding.

Reference parity (SURVEY §4.4, §6.8):
  * SparkDl4jMultiLayer / SharedTrainingMaster driver-executor bootstrap:
    Spark RPC broadcasts config + initial params; Aeron mesh forms for
    gradient exchange; VirtualDataSetIterator partitions data per executor.

TPU-native realization: ``jax.distributed.initialize`` (coordination service
= the driver/parameter-server bootstrap role; rank assignment + barrier),
after which every host runs the SAME SPMD program over the global mesh —
gradient exchange is inside the compiled step (ICI/DCN collectives), not a
transport we operate. Data: deterministic per-host shard assignment
(host_id → slice of files/examples), the VirtualDataSetIterator role.

Multi-host paths are exercised via multi-process CPU tests (SURVEY §5.5
translation); see :func:`launch` for what its workers may touch.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """jax.distributed.initialize wrapper; env-var driven when args absent
    (DL4J_TPU_COORDINATOR / DL4J_TPU_NUM_PROCS / DL4J_TPU_PROC_ID)."""
    import jax

    coordinator_address = coordinator_address or os.environ.get("DL4J_TPU_COORDINATOR")
    if num_processes is None and "DL4J_TPU_NUM_PROCS" in os.environ:
        num_processes = int(os.environ["DL4J_TPU_NUM_PROCS"])
    if process_id is None and "DL4J_TPU_PROC_ID" in os.environ:
        process_id = int(os.environ["DL4J_TPU_PROC_ID"])
    if coordinator_address is None:
        return  # single-process run; nothing to do
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def host_shard(items: Sequence, process_id: Optional[int] = None,
               num_processes: Optional[int] = None) -> list:
    """Deterministic per-host shard of a work list (files, example ranges) —
    the VirtualDataSetIterator partitioning role. host i takes items[i::N]."""
    import jax

    pid = process_id if process_id is not None else jax.process_index()
    n = num_processes if num_processes is not None else jax.process_count()
    return list(items)[pid::n]


class ShardedDataSetIterator:
    """Per-host shard of a dataset iterator.

    When the base iterator supports FILE-level sharding (``shard_files()``,
    e.g. ImageRecordReader), it is sharded ONCE at construction and then
    iterated fully — each host reads/decodes only its 1/N of the data.
    Otherwise this falls back to batch round-robin, which still iterates
    (and pays ETL for) the FULL base on every host — correct but O(global)
    per host; a one-time warning says so (round-4 verdict weak #4)."""

    def __init__(self, base, process_id: Optional[int] = None,
                 num_processes: Optional[int] = None):
        import jax

        self.base = base
        self.pid = process_id if process_id is not None else jax.process_index()
        self.n = num_processes if num_processes is not None else jax.process_count()
        self._file_sharded = False
        if hasattr(base, "shard_files") and self.n > 1:
            if getattr(base, "_dl4j_file_sharded", False):
                raise ValueError(
                    "this reader was already file-sharded by another "
                    "ShardedDataSetIterator — wrapping it twice would "
                    "compound to 1/N² of the data; reuse the first wrapper "
                    "or construct a fresh reader")
            base.shard_files(self.pid, self.n)
            base._dl4j_file_sharded = True
            self._file_sharded = True
        elif self.n > 1:
            import warnings

            warnings.warn(
                "ShardedDataSetIterator: base iterator has no shard_files();"
                " falling back to batch round-robin — every host still runs"
                " the full ETL. Give the reader file-level sharding for"
                " O(global/N) input cost.", stacklevel=2)

    @property
    def batch_size(self):
        return self.base.batch_size

    def reset(self):
        self.base.reset()

    def __iter__(self):
        if self._file_sharded:
            yield from self.base
            return
        for i, ds in enumerate(self.base):
            if i % self.n == self.pid:
                yield ds


# ---------------------------------------------------------------------------
# Multi-process launcher CLI (round 4) — the SharedTrainingMaster JOB role
# (SURVEY §4.4, §8.2-M5): spawn N worker processes that form a
# jax.distributed cluster, stream their output, and on worker failure kill
# the survivors and relaunch the whole job (checkpoint-restart elasticity,
# SURVEY §6.3 — workers resume from their latest checkpoint on restart).
#
#   python -m deeplearning4j_tpu.parallel.launch --nprocs 2 --restarts 1 \
#       -- my_fit_script.py arg1 arg2
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(nprocs: int, argv: Sequence[str], restarts: int = 0,
           env_extra: Optional[dict] = None, timeout: float = 600.0) -> int:
    """Run ``argv`` as ``nprocs`` coordinated worker processes ON THIS HOST.

    Returns the exit code (0 = all workers succeeded on some attempt).
    Each attempt uses a fresh coordinator port; workers read the cluster
    layout from DL4J_TPU_* env vars via initialize_distributed().

    A TPU chip has one owner, and on a TPU host ONE process drives all
    local chips (``ParallelWrapper`` over ``make_mesh()``; across hosts,
    one such process per host, each calling ``initialize_distributed``).
    Several workers on one host would fight over the chips, so with
    ``nprocs > 1`` every worker is pinned to the CPU backend
    (``JAX_PLATFORMS=cpu``): this launcher is the rehearsal and elasticity
    harness of the multi-process protocol, not a way onto the chips.
    ``env_extra`` is applied last and can say otherwise."""
    import subprocess
    import sys
    import time

    for attempt in range(restarts + 1):
        port = _free_port()
        procs = []
        for pid in range(nprocs):
            env = dict(os.environ)
            if nprocs > 1:
                env["JAX_PLATFORMS"] = "cpu"
            env.update(env_extra or {})
            env.update({
                "DL4J_TPU_COORDINATOR": f"127.0.0.1:{port}",
                "DL4J_TPU_NUM_PROCS": str(nprocs),
                "DL4J_TPU_PROC_ID": str(pid),
            })
            procs.append(subprocess.Popen(
                [sys.executable] + list(argv), env=env))
        deadline = time.time() + timeout
        failed = timed_out = False
        while procs:
            for p in list(procs):
                rc = p.poll()
                if rc is None:
                    continue
                procs.remove(p)
                if rc != 0:
                    failed = True
            timed_out = bool(procs) and time.time() > deadline
            if failed or timed_out:
                for p in procs:  # kill survivors (they may be blocked in a
                    p.terminate()  # collective waiting on the dead rank)
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                break
            time.sleep(0.1)
        if not failed and not timed_out and procs == []:
            return 0
        # a timeout is a healthy-but-slow job, not a crash: report it
        # distinctly and do not burn a restart attempt on it (ADVICE r4 #5)
        if timed_out and not failed:
            print(f"[launch] attempt {attempt + 1}: workers exceeded the "
                  f"--timeout of {timeout:.0f}s and were killed (not a "
                  f"worker failure; raise --timeout for long jobs)",
                  flush=True)
            return 124  # conventional timeout exit code
        print(f"[launch] attempt {attempt + 1}/{restarts + 1} failed "
              f"(worker crash)"
              + ("; relaunching (workers resume from checkpoint)"
                 if attempt < restarts else ""),
              flush=True)
    return 1


def main(args: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.parallel.launch",
        description="Multi-process training launcher (SharedTrainingMaster "
                    "job role): coordinates N workers via jax.distributed; "
                    "on failure relaunches so workers resume from their "
                    "latest checkpoint.")
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--restarts", type=int, default=0,
                    help="relaunch attempts after a worker failure")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-attempt wall-clock limit (seconds)")
    ap.add_argument("argv", nargs="+",
                    help="worker script and its args (prefix with --)")
    ns = ap.parse_args(args)
    return launch(ns.nprocs, ns.argv, restarts=ns.restarts,
                  timeout=ns.timeout)


if __name__ == "__main__":
    raise SystemExit(main())


def distributed_evaluate(net, iterator, evaluation=None):
    """Cluster-wide evaluation (the dl4j-spark RDD ``doEvaluation`` role,
    SURVEY §3.3): every process evaluates ITS shard of ``iterator``
    (typically a ShardedDataSetIterator), then the per-process Evaluation
    states merge across the jax.distributed cluster — counts are summed via
    an all-gather of the confusion matrix, so every rank returns the same
    global Evaluation. Single-process runs degrade to plain evaluate()."""
    import jax

    local = net.evaluate(iterator, evaluation=evaluation)
    if jax.process_count() == 1:
        return local
    from jax.experimental import multihost_utils

    # EVERY rank must execute the SAME collectives in the same order (a
    # zero-batch rank running a different sequence would deadlock the
    # cluster): first agree on num_classes, then gather fixed-shape
    # confusion matrices (zero-padded on ranks that saw fewer classes /
    # no batches).
    local_n = 0 if local.num_classes is None else int(local.num_classes)
    n = int(multihost_utils.process_allgather(np.asarray(local_n)).max())
    conf = np.zeros((n, n), np.int64)
    if local.confusion is not None:
        ln = local.confusion.shape[0]
        conf[:ln, :ln] = local.confusion
    gathered = multihost_utils.process_allgather(conf)
    local.num_classes = n
    local.confusion = np.asarray(gathered).sum(axis=0)
    return local
