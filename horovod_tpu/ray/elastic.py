"""Elastic Ray integration: fault-tolerant jobs on Ray actors.

TPU-native rebuild of the reference's unified elastic Ray executor
(``/root/reference/horovod/ray/elastic_v2.py:1-547`` and ``elastic.py``):
host discovery reads Ray's live cluster state, workers run as actors
pinned to discovered nodes, and dead nodes are replaced mid-run. The
rebuild reuses the framework's elastic core unchanged — the
:class:`~horovod_tpu.elastic.driver.ElasticDriver` round protocol, the
signed KV rendezvous, blacklisting, and the worker-side
``hvd.elastic.run`` state recovery all behave exactly as under
``hvdrun --min-np``; Ray replaces only *process placement* (the same
design split as the static :class:`~horovod_tpu.ray.runner.RayExecutor`).

    from horovod_tpu.ray import ElasticRayExecutor

    ex = ElasticRayExecutor(min_workers=2, max_workers=8)
    ex.start()
    results = ex.run(train_fn)   # fn uses hvd.elastic.run internally
    ex.shutdown()
"""

from __future__ import annotations

import threading
from typing import Any, Callable

from ..runner import hosts as hosts_mod
from ..runner.launch import worker_env
from ..utils import logging as hvd_logging


class RayHostDiscovery:
    """Discover usable hosts from Ray's cluster state (reference
    ``RayHostDiscovery``, ``elastic_v2.py``): every alive node contributes
    ``floor(node_cpus / cpus_per_worker)`` slots, optionally bounded by
    custom resource requirements. Plugs into the elastic driver's
    ``HostManager`` exactly like a discovery script."""

    def __init__(self, ray_module, cpus_per_worker: int = 1,
                 resources_per_worker: dict | None = None,
                 max_slots_per_host: int | None = None):
        self._ray = ray_module
        self.cpus_per_worker = max(int(cpus_per_worker), 1)
        self.resources_per_worker = dict(resources_per_worker or {})
        self.max_slots_per_host = max_slots_per_host

    def find_available_hosts_and_slots(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for node in self._ray.nodes():
            if not node.get("Alive"):
                continue
            host = node.get("NodeManagerAddress")
            res = node.get("Resources", {}) or {}
            slots = int(res.get("CPU", 0) // self.cpus_per_worker)
            for name, need in self.resources_per_worker.items():
                if need > 0:
                    slots = min(slots, int(res.get(name, 0) // need))
            if self.max_slots_per_host is not None:
                slots = min(slots, self.max_slots_per_host)
            if host and slots > 0:
                out[host] = slots
        return out


class _ActorProcess:
    """Adapt a (Ray actor, in-flight ObjectRef) pair to the process-handle
    contract the elastic driver supervises (``poll``/``wait``/
    ``terminate`` with exit codes, like ``safe_exec.ExecutedProcess``).
    ``sys.exit(code)`` inside the worker fn (the slot-lost self-exit path)
    maps onto the same codes a subprocess worker would return."""

    def __init__(self, ray_module, actor, ref):
        self._ray = ray_module
        self._actor = actor
        self._ref = ref
        self._code: int | None = None
        self._result: Any = None

    def _settle(self, timeout: float | None) -> int | None:
        if self._code is not None:
            return self._code
        done, _ = self._ray.wait([self._ref], timeout=timeout)
        if not done:
            return None
        try:
            status, payload = self._ray.get(self._ref)
            if status == "ok":
                self._code, self._result = 0, payload
            else:  # ("exit", code) — worker self-exited
                self._code = int(payload)
        except Exception as e:
            hvd_logging.debug("elastic ray worker raised: %s", e)
            self._code = 1
        return self._code

    def poll(self) -> int | None:
        return self._settle(0)

    def wait(self, timeout: float | None = None) -> int:
        code = self._settle(timeout)
        if code is None:
            raise TimeoutError("ray worker still running")
        return code

    def result(self):
        return self._result

    def terminate(self) -> None:
        if self._code is None:
            self._code = 143
        try:
            self._ray.kill(self._actor)
        except Exception:  # hvdlint: disable=silent-except
            pass  # actor already dead / cluster gone at terminate


class _ElasticWorker:
    """One elastic rank: seeds the launcher env then runs the user fn
    (which drives ``hvd.elastic.run`` / ``WorkerRendezvous`` exactly as a
    subprocess worker would)."""

    def execute(self, env: dict, fn, args, kwargs):
        import os
        os.environ.update(env)
        try:
            return ("ok", fn(*args, **(kwargs or {})))
        except SystemExit as e:  # slot-lost / driver-stop self-exit
            return ("exit", int(e.code or 0))


def _make_elastic_worker_cls(ray_module=None):
    """Worker class hook (tests substitute an env-passing variant)."""
    return _ElasticWorker


class ElasticRayExecutor:
    """Elastic job on Ray actors (reference ``ElasticRayExecutor``,
    ``elastic_v2.py:260-547``). The user fn must wrap its training loop in
    ``hvd.elastic.run`` (state commit/restore), exactly as under elastic
    ``hvdrun``."""

    def __init__(self, min_workers: int, max_workers: int | None = None,
                 *, cpus_per_worker: int = 1,
                 resources_per_worker: dict | None = None,
                 env_vars: dict | None = None,
                 elastic_timeout: float | None = None,
                 reset_limit: int | None = None,
                 override_discovery=None):
        self.min_workers = int(min_workers)
        self.max_workers = int(max_workers) if max_workers else None
        self.cpus_per_worker = cpus_per_worker
        self.resources_per_worker = dict(resources_per_worker or {})
        self.env_vars = dict(env_vars or {})
        self.elastic_timeout = elastic_timeout
        self.reset_limit = reset_limit
        self._override_discovery = override_discovery
        self._ray = None
        self._infra = None
        self._driver = None
        self._worker_cls = None
        self._handles: dict = {}
        self._handles_lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        import ray  # lazy; the module imports without Ray installed

        self._ray = ray
        if not ray.is_initialized():
            ray.init()

    def _spawn(self, slot: hosts_mod.SlotInfo, env: dict, fn, args,
               kwargs) -> _ActorProcess:
        ray = self._ray
        if self._worker_cls is None:
            # one remote-class registration per executor, not per spawn
            self._worker_cls = ray.remote(_make_elastic_worker_cls(ray))
        worker_cls = self._worker_cls
        opts: dict = {"num_cpus": self.cpus_per_worker}
        resources = dict(self.resources_per_worker)
        # Ray's per-node custom resource pins the actor to the discovered
        # host (the reference pins with the same node resource,
        # elastic_v2.py worker placement).
        resources[f"node:{slot.hostname}"] = 0.001
        opts["resources"] = resources
        actor = worker_cls.options(**opts).remote()
        ref = actor.execute.remote(env, fn, args, kwargs)
        handle = _ActorProcess(ray, actor, ref)
        with self._handles_lock:
            self._handles[(slot.hostname, slot.local_rank)] = handle
        return handle

    def run(self, fn: Callable, args=(), kwargs: dict | None = None) -> list:
        """Run the elastic job; returns the results of the workers that
        completed the final round successfully (reference
        ``ElasticRayExecutor.run``)."""
        if self._ray is None:
            raise RuntimeError("ElasticRayExecutor.start() has not been "
                               "called")
        with self._handles_lock:
            self._handles.clear()  # a prior run()'s workers must not leak
        from ..elastic.bootstrap import make_elastic_infra

        discovery = self._override_discovery or RayHostDiscovery(
            self._ray, self.cpus_per_worker, self.resources_per_worker)
        infra = make_elastic_infra(
            discovery, self.min_workers, self.max_workers,
            timeout=self.elastic_timeout, reset_limit=self.reset_limit)
        self._infra = infra
        self._driver = infra.driver

        def create_worker_fn(slot: hosts_mod.SlotInfo, spec_round: int):
            spec = infra.round_spec(spec_round)
            env = worker_env(
                slot,
                coordinator_addr=spec["coord_addr"],
                coordinator_port=spec["coord_port"],
                kv_addr=infra.kv_addr, kv_port=infra.kv_port,
                secret=infra.secret,
                extra=infra.worker_extra_env(spec_round, self.env_vars))
            return self._spawn(slot, env, fn, args, kwargs)

        try:
            infra.driver.start(self.min_workers, create_worker_fn)
            infra.driver.join()
            results = infra.driver.get_results()
            if results.error_message:
                raise RuntimeError(
                    f"elastic ray job failed: {results.error_message}")
            if not infra.driver.succeeded:
                raise RuntimeError("elastic ray job stopped without a "
                                   "successful worker")
            # Only workers holding a slot in the FINAL round contribute
            # results: a worker from an earlier shrunk round that exited 0
            # on a slot the last round never reused would otherwise inject
            # a stale/duplicate result.
            final_slots = {(s.hostname, s.local_rank)
                           for slots in infra.driver.host_assignments.values()
                           for s in slots}
            out = []
            with self._handles_lock:
                for key, handle in self._handles.items():
                    if key in final_slots and handle.poll() == 0:
                        out.append(handle.result())
            return out
        finally:
            infra.stop()
            self._infra = None

    def shutdown(self) -> None:
        with self._handles_lock:
            for handle in self._handles.values():
                handle.terminate()
            self._handles.clear()
        if self._infra is not None:
            self._infra.stop()
            self._infra = None
