"""Spark integration: run framework jobs as barrier-mode Spark tasks.

TPU-native rebuild of the reference's ``horovod.spark.run()``
(``/root/reference/horovod/spark/runner.py:199-430``: one Spark task per
rank, a driver service for registration/address exchange, results returned
per rank). The rebuild is deliberately thin and Spark-native:

* **Placement** comes from Spark's barrier scheduling
  (``RDD.barrier().mapPartitions``) — all ``num_proc`` tasks start
  together or not at all, the property the reference builds by hand with
  its start-timeout polling loop.
* **Registration / address exchange** uses ``BarrierTaskContext.allGather``
  (every task shares its IP and rank 0 its coordinator port) instead of
  the reference's driver-service RPC registration
  (``spark/driver/driver_service.py``).
* **Rendezvous** reuses the ``hvdrun`` launcher's signed KV server on the
  Spark driver and the same ``HVD_*`` env contract
  (``runner/launch.py:202-343``) — identical to the Ray integration, so a
  job launched from Spark, Ray, or ``hvdrun`` initializes identically.

    import horovod_tpu.spark

    results = horovod_tpu.spark.run(train_fn, args=(cfg,), num_proc=4)

The reference's Petastorm machinery (``horovod/spark/keras``,
``spark/lightning`` adapting Parquet stores to TF/Torch DataLoaders) is a
documented non-goal — it has no analog in the jax input pipeline. The
estimator *role* itself (train from data, Store-backed checkpoints,
resume) IS covered by the lite bridge in
:mod:`horovod_tpu.spark.estimator`: :func:`fit`, :func:`fit_dataframe`,
:func:`save_dataset`. pyspark is imported lazily: the module imports
fine without Spark installed.
"""

from __future__ import annotations

import json
import os
import queue
import threading
from typing import Any, Callable

from ..runner import hosts as hosts_mod
from ..runner.http_kv import KVServer, local_addresses, make_secret
from ..runner.launch import _free_port, worker_env
from ..utils import envs

DEFAULT_START_TIMEOUT_S = 600.0
_REGISTER_SCOPE = "spark/registered"


def _task_body(fn, args, kwargs, secret, kv_addr, kv_port, extra_env):
    """Runs inside every barrier task: exchange placement, seed the
    launcher env contract, run the user function as this rank."""
    from pyspark import BarrierTaskContext

    from ..runner.http_kv import KVClient

    ctx = BarrierTaskContext.get()
    rank = ctx.partitionId()

    import socket
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect((kv_addr, int(kv_port)))
            my_ip = s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        my_ip = socket.gethostbyname(socket.gethostname())

    # one allGather round: IPs of every task + rank 0's coordinator port
    # (the reference's task-to-task address registration,
    # spark/runner.py:281-303, collapsed into Spark's own primitive)
    coord_port = _free_port() if rank == 0 else 0
    entries = [json.loads(e) for e in ctx.allGather(
        json.dumps({"rank": rank, "ip": my_ip, "coord_port": coord_port}))]
    entries.sort(key=lambda e: e["rank"])
    ips = [e["ip"] for e in entries]
    slots = hosts_mod.slots_from_ips(ips)

    env = worker_env(slots[rank], coordinator_addr=ips[0],
                     coordinator_port=entries[0]["coord_port"],
                     kv_addr=kv_addr, kv_port=kv_port, secret=secret,
                     extra=extra_env)
    os.environ.update(env)
    # Registration mark: once every rank has reported in, the driver stops
    # counting against start_timeout — the timeout bounds task SCHEDULING
    # only, never the training itself (reference start_timeout semantics,
    # spark/runner.py:210-214).
    KVClient(kv_addr, int(kv_port), secret=secret).put(
        f"{_REGISTER_SCOPE}/{rank}", b"1")
    return [(rank, fn(*args, **(kwargs or {})))]


def run(fn: Callable, args=(), kwargs: dict | None = None,
        num_proc: int | None = None, start_timeout: float | None = None,
        env: dict | None = None, verbose: int = 1) -> list:
    """Run ``fn(*args, **kwargs)`` as ``num_proc`` ranks on Spark executors
    and return the per-rank results, rank-ordered (reference
    ``horovod.spark.run``, ``spark/runner.py:199-430``).

    ``num_proc`` defaults to ``spark.default.parallelism``;
    ``start_timeout`` (or ``HVD_SPARK_START_TIMEOUT``) bounds how long the
    barrier tasks may take to be scheduled and finish, and ``env`` adds
    extra variables to every rank's environment.
    """
    import pyspark

    sc = pyspark.SparkContext._active_spark_context
    if sc is None:
        raise RuntimeError(
            "horovod_tpu.spark.run() needs an active SparkContext — start "
            "a SparkSession first (the reference requires the same, "
            "spark/runner.py:251-254)")
    if num_proc is None:
        num_proc = sc.defaultParallelism
    num_proc = int(num_proc)
    if start_timeout is None:
        start_timeout = envs.get_float(envs.SPARK_START_TIMEOUT,
                                       DEFAULT_START_TIMEOUT_S)

    secret = make_secret()
    kv = KVServer(secret=secret)
    kv_port = kv.start()
    kv_addr = local_addresses()[0]
    extra_env = dict(env or {})

    task = _make_task(fn, tuple(args), kwargs, secret, kv_addr, kv_port,
                      extra_env)
    result_q: queue.Queue = queue.Queue(1)
    group = f"horovod_tpu.spark.run.{os.getpid()}.{id(task):x}"

    def _drive():
        try:
            sc.setJobGroup(group, "horovod_tpu.spark.run",
                           interruptOnCancel=True)
            rdd = sc.parallelize(range(num_proc), num_proc)
            result_q.put(("ok", rdd.barrier().mapPartitions(task).collect()))
        except BaseException as e:  # surfaced on the caller thread
            result_q.put(("error", e))

    thread = threading.Thread(target=_drive, daemon=True,
                              name="hvd-spark-driver")
    thread.start()
    try:
        # Phase 1 — startup, bounded by start_timeout: every task must
        # register through the KV. Phase 2 — training, unbounded: once all
        # ranks are running, the job takes as long as fn takes (the
        # reference's start_timeout covers scheduling only).
        import time as _time
        deadline = _time.monotonic() + start_timeout
        status = payload = None
        while len(kv.keys(_REGISTER_SCOPE)) < num_proc:
            try:
                status, payload = result_q.get(timeout=0.2)
                break  # collect() finished (or failed) before registration
            except queue.Empty:
                pass
            if _time.monotonic() > deadline:
                try:
                    sc.cancelJobGroup(group)
                except Exception:  # hvdlint: disable=silent-except
                    pass  # best-effort cancel; the TimeoutError below is
                    # the real signal
                raise TimeoutError(
                    f"horovod_tpu.spark.run timed out after {start_timeout}s "
                    f"waiting for {num_proc} barrier tasks to start; check "
                    "that the cluster has enough simultaneous slots "
                    "(barrier mode schedules all-or-nothing) or raise "
                    "start_timeout/HVD_SPARK_START_TIMEOUT")
        if status is None:
            status, payload = result_q.get()
        if status == "error":
            raise payload
        pairs = sorted(payload, key=lambda rv: rv[0])
        if [r for r, _ in pairs] != list(range(num_proc)):
            raise RuntimeError(
                f"spark run returned ranks {[r for r, _ in pairs]}, "
                f"expected 0..{num_proc - 1}")
        return [v for _, v in pairs]
    finally:
        # Orderly teardown: cancelJobGroup is best-effort and the daemon
        # _drive thread may still sit in collect(); give the cancellation
        # a moment to unwind before the KV dies, so straggler barrier
        # tasks fail against a cancelled job, not a vanished KV.
        thread.join(timeout=10.0)
        kv.stop()


def _make_task(fn, args, kwargs, secret, kv_addr, kv_port, extra_env):
    """Build the mapPartitions closure (kept top-level so everything it
    captures is explicit and cloudpickle-friendly)."""
    def _task(_iterator) -> Any:
        return _task_body(fn, args, kwargs, secret, kv_addr, kv_port,
                          extra_env)
    return _task


from .estimator import fit, fit_dataframe, save_dataset  # noqa: E402
