"""Process-wide runtime state: device mesh, ranks, process sets.

TPU-native replacement for the reference's ``HorovodGlobalState`` singleton +
init path (``/root/reference/horovod/common/global_state.h:39-126``,
``InitializeHorovodOnce`` at ``/root/reference/horovod/common/operations.cc:811-864``)
and the Python facade ``HorovodBasics``
(``/root/reference/horovod/common/basics.py:48-146,373-468``).

Design inversion (SURVEY.md §7): there is no background negotiation thread.
Under SPMD the program order of collectives is identical on every rank by
construction, so init reduces to (a) optional ``jax.distributed.initialize``
rendezvous, (b) building a rank-ordered global ``jax.sharding.Mesh``, and
(c) registering the global process set. A *rank* is a TPU chip (device), not
a host process: one controller process drives ``local_size`` chips.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from . import timeline as _timeline
from .loopback import context as _lbctx
from .utils import envs
from .utils import logging as hvd_logging

# program span (docs/timeline.md): the whole of hvd.init(). It runs
# before any profiler session, so it is read from hvd_span_seconds.
_INIT = _timeline.span("init")

# The canonical mesh axis name for the flat data-parallel "rank" axis.
AXIS_NAME = "hvd"


class NotInitializedError(RuntimeError):
    """Raised when the API is used before ``hvd.init()`` (reference raises
    from ``CheckInitialized``, ``operations.cc:904-910``)."""


@dataclasses.dataclass
class _RuntimeState:
    devices: list  # rank-ordered global device list; rank == index
    mesh: Mesh  # 1-D mesh over `devices` with axis AXIS_NAME
    axis_name: str
    process_index: int
    process_count: int
    local_ranks: list  # global ranks owned by this process
    process_set_table: Any  # ProcessSetTable (import cycle avoided)
    # Loopback worlds: rank -> owning (virtual) process. In a real world
    # the mapping comes from each device's process_index; loopback ranks
    # share one interpreter whose fake CPU devices all report process 0,
    # so the world records the virtual mapping explicitly.
    rank_process_map: list | None = None


_state: _RuntimeState | None = None
_lock = threading.Lock()
# Bumped on every successful init(); lets cached per-ProcessSet meshes
# detect a shutdown()/init() cycle and rebuild over fresh device objects.
_generation = 0


def _rank_ordered_devices(devices=None):
    """Global devices ordered so rank = process-major, local-minor.

    Mirrors the reference rank layout where ranks are contiguous per host
    (``gloo_run.py:65-101`` seeds HOROVOD_RANK host-major)."""
    devs = list(devices if devices is not None else jax.devices())
    devs.sort(key=lambda d: (d.process_index, d.id))
    return devs


def init(
    comm: Sequence[int] | None = None,
    process_sets: Sequence[Sequence[int]] | str | None = None,
    *,
    devices=None,
    axis_name: str = AXIS_NAME,
) -> None:
    """Initialize the runtime (reference: ``hvd.init`` → ``horovod_init``,
    ``operations.cc:889-899``).

    Args:
      comm: optional list of global ranks forming the *global* process set
        (reference accepts a rank list at ``basics.py:48-146``). Default: all.
      process_sets: optional list of rank-lists to register as additional
        process sets at init time, or the string ``"dynamic"`` to enable
        dynamic registration (reference gates this on
        ``HOROVOD_DYNAMIC_PROCESS_SETS``, ``operations.cc:606-607``).
      devices: explicit device list (testing hook).
      axis_name: mesh axis name used by every collective.
    """
    with _INIT():
        _init(comm, process_sets, devices, axis_name)


def _init(comm, process_sets, devices, axis_name: str) -> None:
    from . import conformance as _conformance
    # the lockstep recorder's cached gate re-reads HVD_CONFORMANCE at
    # init so launcher-seeded (or test-set) knobs engage without an
    # import-order dance (docs/conformance.md)
    _conformance.refresh()
    ctx = _lbctx.current()
    if ctx is not None:
        _loopback_init(ctx, axis_name=axis_name, process_sets=process_sets)
        return
    if envs.get_bool(envs.LOOPBACK):
        # Satellite fix (ISSUE 10): a half-configured loopback env — the
        # HVD_LOOPBACK marker without a rank context (e.g. exported
        # manually, or a loopback worker env leaked into a plain
        # process) — must fail HERE with a clear message. Proceeding
        # would treat the leaked HVD_KV_*/HVD_NUM_PROCESSES contract as
        # a real multi-process launch and hang on KV connect.
        raise RuntimeError(
            "HVD_LOOPBACK=1 is set but this thread has no loopback rank "
            "context. Loopback worlds are created with "
            "hvd.loopback.world(n) (or `hvdrun --loopback`); do not "
            "export HVD_LOOPBACK/HVD_KV_* by hand. Unset HVD_LOOPBACK "
            "to run as a normal process.")
    global _state, _generation
    with _lock:
        if _state is not None:
            hvd_logging.debug("init() called twice; ignoring")
            return
        # re-init epoch, not telemetry (keys cache invalidation)
        _generation += 1  # hvdlint: disable=metrics-registry

        _maybe_distributed_init()

        devs = _rank_ordered_devices(devices)
        if comm is not None:
            devs = [devs[r] for r in comm]
        mesh = Mesh(np.array(devs), (axis_name,))

        proc_index = jax.process_index()
        local_ranks = [i for i, d in enumerate(devs) if d.process_index == proc_index]

        from .process_sets import ProcessSetTable  # deferred: avoids cycle

        table = ProcessSetTable()
        _state = _RuntimeState(
            devices=devs,
            mesh=mesh,
            axis_name=axis_name,
            process_index=proc_index,
            process_count=jax.process_count(),
            local_ranks=local_ranks,
            process_set_table=table,
        )
        table.initialize_global(len(devs))

        dynamic = process_sets == "dynamic" or envs.get_bool(envs.DYNAMIC_PROCESS_SETS)
        table.dynamic_enabled = dynamic
        if process_sets and process_sets != "dynamic":
            for ranks in process_sets:
                table.add(list(ranks), force=True)

        hvd_logging.info(
            "initialized: %d chips across %d processes (this=%d, local=%s)",
            len(devs), _state.process_count, proc_index, local_ranks,
        )
    # Outside the lock: timeline autostart builds the native engine.
    _timeline.maybe_autostart()
    # Per-worker Prometheus exposition when HVD_METRICS_PORT is seeded
    # (hvdrun --metrics-port); idempotent across elastic re-inits.
    from . import metrics as _metrics
    _metrics.maybe_serve()
    # Multi-process jobs start the negotiation service now (the analog of
    # the reference spawning BackgroundThreadLoop inside init,
    # operations.cc:811-864): every process must tick cycles even before
    # its first collective, or peers' exchanges block and stalls go
    # undetected.
    from . import engine_service as _engine_service
    _engine_service.get_service()


def _loopback_init(ctx, *, axis_name: str = AXIS_NAME,
                   process_sets=None) -> None:
    """``init()`` on a loopback rank thread: build this rank's world view
    from its env overlay — no ``jax.distributed``, no cross-process XLA
    program, ever. The negotiation service (real KV wire format) starts
    immediately, exactly like the multi-process init path."""
    if ctx.runtime_state is not None:
        hvd_logging.debug("loopback init() called twice; ignoring")
        return
    missing = [v for v in (envs.NUM_PROCESSES, envs.PROCESS_ID,
                           envs.KV_ADDR, envs.KV_PORT)
               if envs.get(v) is None]
    if missing:
        raise RuntimeError(
            "loopback rank context is half-configured: missing "
            f"HVD_{'/HVD_'.join(missing)}. Loopback worlds seed the full "
            "launcher contract via hvd.loopback.world(n); refusing to "
            "init rather than hang on KV connect (docs/loopback.md).")
    size = int(envs.require(envs.NUM_PROCESSES))
    rank = int(envs.require(envs.PROCESS_ID))
    if not 0 <= rank < size:
        raise RuntimeError(
            f"loopback rank {rank} out of range for world size {size}")
    from .loopback.engine import _check_devices
    _check_devices(size)  # shared check + XLA_FLAGS hint
    devs = _rank_ordered_devices(None)[:size]
    mesh = Mesh(np.array(devs), (axis_name,))
    from .process_sets import ProcessSetTable
    table = ProcessSetTable()
    ctx.generation += 1
    ctx.runtime_state = _RuntimeState(
        devices=devs, mesh=mesh, axis_name=axis_name,
        process_index=rank, process_count=size, local_ranks=[rank],
        process_set_table=table, rank_process_map=list(range(size)))
    table.initialize_global(size)
    # Drop hub occurrence tables from previous world incarnations: an
    # elastic re-form re-seeds the coordinator scope, so the old scopes'
    # slot ids can never recur (loopback/dispatch.prune_stale_scopes).
    from .loopback import dispatch as _lbdispatch
    _lbdispatch.prune_stale_scopes(ctx)
    dynamic = (process_sets == "dynamic"
               or envs.get_bool(envs.DYNAMIC_PROCESS_SETS))
    table.dynamic_enabled = dynamic
    if process_sets and process_sets != "dynamic":
        for ranks in process_sets:
            table.add(list(ranks), force=True)
    hvd_logging.info(
        "loopback initialized: rank %d of %d (world %s)", rank, size,
        envs.get(envs.COORDINATOR_ADDR, "?"))
    # HVD_TIMELINE works in loopback worlds too: the first rank's init
    # starts the one shared writer; every rank's events carry a
    # rank<N>/ lane prefix (the ISSUE-11 attribution fix).
    _timeline.maybe_autostart()
    from . import engine_service as _engine_service
    _engine_service.get_service()
    # Elastic warm re-form: adopt the shelf entry for this exact shape
    # (world scope, size, rank) as the warm pool — plan builds from here
    # on graft the shelved incarnation's compiled stages when their
    # re-derived negotiation names match (ops/dispatch_cache.py).
    from .ops import dispatch_cache as _dispatch_cache
    warm = _dispatch_cache.restore_for_reform()
    if warm:
        hvd_logging.info(
            "loopback init: %d shelved dispatch plans warm for rank %d "
            "of %d", warm, rank, size)


def _distributed_client_active() -> bool:
    return _distributed_kv_client() is not None


def _maybe_distributed_init() -> None:
    """Bootstrap ``jax.distributed`` from launcher-seeded env, the analog of
    the reference rendezvous (``GlooContext::Initialize`` reading
    ``HOROVOD_GLOO_RENDEZVOUS_ADDR``, ``gloo_context.h:29-42``). Jobs
    launched by ``srun``/``mpirun`` instead of ``hvdrun`` (the reference's
    primary launch modes, ``mpi_run.py``/``lsf.py``) are auto-detected:
    jax's own cluster detection joins the world, and the negotiation KV is
    bootstrapped over jax's distributed key-value store
    (:func:`_maybe_bootstrap_kv`).

    NOTE: must run before anything touches the XLA backend — we avoid any
    jax query here and check env + the distributed client state only.
    """
    addr = envs.get(envs.COORDINATOR_ADDR)
    num_proc = envs.get_int(envs.NUM_PROCESSES, 1)
    if _distributed_client_active():
        _maybe_bootstrap_kv()
        return
    if addr is None or num_proc <= 1:
        _maybe_cluster_autodetect()
        return
    port = envs.get(envs.COORDINATOR_PORT, "9778")
    proc_id = envs.get_int(envs.PROCESS_ID, 0)
    if envs.get_bool(envs.ELASTIC):
        # A peer crash must not fatally poison the coordination service:
        # recoverability keeps the shutdown barrier and error polling from
        # terminating surviving workers, so hvd.elastic can rebuild the
        # world instead (the analog of the reference's elastic
        # AsyncErrorCheck path, ``nccl_operations.cc:126-140``).
        jax.config.update("jax_enable_recoverability", True)
    try:
        jax.distributed.initialize(
            coordinator_address=f"{addr}:{port}",
            num_processes=num_proc,
            process_id=proc_id,
        )
    except RuntimeError as e:
        # Either the backend was already initialized by earlier user code
        # (jax.distributed must come first) or the coordinator is
        # unreachable. Carrying on would train as an unsynchronised
        # single-host world that still exits 0.
        raise RuntimeError(
            f"jax.distributed.initialize failed for process {proc_id}/"
            f"{num_proc} via {addr}:{port} ({e}). Call hvd.init() before "
            "any other jax API, or pre-initialize jax.distributed "
            "yourself.") from e
    hvd_logging.info("jax.distributed initialized: process %d/%d via %s:%s",
                     proc_id, num_proc, addr, port)
    _maybe_bootstrap_kv()


# (world-size var, per-process rank var): the rank var is only set inside
# an actual srun/mpirun/jsrun task — an `#SBATCH --ntasks=8` script running
# plain `python` exports SLURM_NTASKS but no SLURM_PROCID, and must NOT
# trigger a blocking multi-process join. JSM_* is IBM JSM, what `jsrun`
# sets on LSF clusters (reference `js_run.py:1-151`).
_CLUSTER_ENV_PAIRS = (("SLURM_NTASKS", "SLURM_PROCID"),
                      ("OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK"),
                      ("PMI_SIZE", "PMI_RANK"),
                      ("JSM_NAMESPACE_SIZE", "JSM_NAMESPACE_RANK"))


def _cluster_world_hint() -> int:
    """World size advertised by a cluster scheduler's env (srun / mpirun /
    PMI), 1 when none — or when only the batch-level var is present
    without the per-task rank var."""
    for world_var, rank_var in _CLUSTER_ENV_PAIRS:
        val = os.environ.get(world_var)
        if val and os.environ.get(rank_var) is not None:
            try:
                return int(val)
            except ValueError:
                pass
    return 1


def _jsm_init_kwargs() -> dict:
    """Explicit ``jax.distributed.initialize`` kwargs for ``jsrun``-launched
    tasks. jax's built-in cluster detection covers SLURM and Open MPI but
    not IBM JSM, so when only JSM env is present the coordinator is derived
    from the LSF allocation itself: rank 0 lives on the first host of
    ``LSB_DJOB_RANKFILE`` (reference jsrun host source, ``js_run.py``;
    rankfile parsing shared with :mod:`horovod_tpu.runner.lsf`). Returns
    ``{}`` (let jax auto-detect) when JSM env is absent or another
    supported scheduler's rank var is also present."""
    if os.environ.get("JSM_NAMESPACE_RANK") is None:
        return {}
    if (os.environ.get("SLURM_PROCID") is not None
            or os.environ.get("OMPI_COMM_WORLD_RANK") is not None):
        return {}  # jax's own detectors know these; prefer them
    from .runner import lsf as lsf_mod
    first_host = lsf_mod.lsf_host_specs()[0].hostname
    port = envs.get(envs.COORDINATOR_PORT, "9778")
    return dict(
        coordinator_address=f"{first_host}:{port}",
        num_processes=int(os.environ["JSM_NAMESPACE_SIZE"]),
        process_id=int(os.environ["JSM_NAMESPACE_RANK"]),
    )


def _maybe_cluster_autodetect() -> None:
    """`srun python train.py` / `mpirun -np N python train.py` parity:
    when a scheduler advertises a multi-process world and no launcher env
    is present, let jax's built-in cluster detection (SLURM / Open MPI)
    join the world, then bootstrap the negotiation KV."""
    if _cluster_world_hint() <= 1:
        return
    kwargs = _jsm_init_kwargs()  # jsrun/LSF: jax has no JSM detector
    try:
        jax.distributed.initialize(**kwargs)  # jax auto-detects SLURM/OMPI
    except (RuntimeError, ValueError) as e:
        raise RuntimeError(
            "cluster env advertises a multi-process world but "
            f"jax.distributed auto-detection failed ({e}). Launch with "
            "hvdrun, or pre-initialize jax.distributed yourself.") from e
    hvd_logging.info(
        "jax.distributed auto-initialized from cluster env: "
        "process %d/%d", jax.process_index(), jax.process_count())
    _maybe_bootstrap_kv()


_bootstrap_kv_server = None  # keep-alive for the process-0 KV server
_bootstrap_seeded_env = False  # whether WE seeded HVD_KV_* (vs a launcher)
_KV_BOOTSTRAP_KEY = "hvd/kv_bootstrap/{}"  # per-generation: re-init safe


def _distributed_kv_client():
    """jax's distributed key-value client (None when unavailable)."""
    try:
        from jax._src import distributed as _dist
        return _dist.global_state.client
    except Exception:  # pragma: no cover - private API moved
        return None


def _kv_advertise_address() -> str:
    """The address peers should dial for the bootstrap KV server: the NIC
    that routes to the jax.distributed coordinator (UDP-connect trick, no
    packet leaves the host), because on multi-NIC hosts the first entry of
    ``local_addresses()`` may be unroutable from peers and negotiation
    would silently hang. Falls back to ``local_addresses()[0]``
    when no coordinator is known."""
    import socket

    coord = None
    try:
        from jax._src import distributed as _dist
        coord = _dist.global_state.coordinator_address
    except Exception:  # pragma: no cover  # hvdlint: disable=silent-except
        pass  # private API probe: absence falls through to the env knob
    if not coord:
        addr = envs.get(envs.COORDINATOR_ADDR)
        if addr:
            coord = f"{addr}:{envs.get(envs.COORDINATOR_PORT, '9778')}"
    if coord:
        host, _, port = coord.rpartition(":")
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                s.connect((host or coord, int(port) if port.isdigit() else 9778))
                return s.getsockname()[0]
            finally:
                s.close()
        except OSError:
            pass
    from .runner.http_kv import local_addresses
    return local_addresses()[0]


def _maybe_bootstrap_kv() -> None:
    """Stand up the negotiation/rendezvous KV for worlds NOT launched by
    ``hvdrun`` (srun/mpirun/user-initialized jax.distributed): process 0
    starts a :class:`KVServer` and publishes ``addr:port:secret`` through
    jax's distributed KV store; everyone seeds the usual ``HVD_KV_*`` env
    so the dynamic engine and elastic plumbing work identically to a
    launcher job. The exchange key carries the init generation, so an
    init/shutdown/init cycle publishes fresh coordinates instead of
    colliding with (or reusing) the previous world's."""
    global _bootstrap_kv_server, _bootstrap_seeded_env
    if envs.get(envs.KV_ADDR):
        return  # launcher already provided one
    client = _distributed_kv_client()
    if client is None or jax.process_count() <= 1:
        return  # nothing to negotiate in a single-process world
    key = _KV_BOOTSTRAP_KEY.format(_generation)
    try:
        if jax.process_index() == 0:
            from .runner.http_kv import KVServer, make_secret
            secret = make_secret()
            server = KVServer(secret=secret)
            port = server.start()
            _bootstrap_kv_server = server
            payload = f"{_kv_advertise_address()}:{port}:{secret}"
            client.key_value_set(key, payload)
        else:
            payload = client.blocking_key_value_get(key, 60_000)
        addr, port, secret = payload.split(":", 2)
        envs.set_env(envs.KV_ADDR, addr)
        envs.set_env(envs.KV_PORT, port)
        envs.set_env(envs.SECRET_KEY, secret)
        _bootstrap_seeded_env = True
        hvd_logging.info("negotiation KV bootstrapped at %s:%s", addr, port)
    except Exception as e:
        hvd_logging.warning(
            "could not bootstrap the negotiation KV over jax's distributed "
            "store (%s); multi-process eager collectives will run without "
            "negotiation (mismatches hang instead of erroring)", e)


def shutdown() -> None:
    """Tear down the runtime (reference ``horovod_shutdown``,
    ``operations.cc:926-942``). Also stops the negotiation service — it is
    bound to this world's size/rank/KV prefix and must be rebuilt by the
    next init()."""
    ctx = _lbctx.current()
    if ctx is not None:
        _loopback_shutdown(ctx)
        return
    global _state, _bootstrap_kv_server, _bootstrap_seeded_env
    from . import autotune as _autotune
    from . import conformance as _conformance
    from . import engine_service as _engine_service
    from .ops import dispatch_cache as _dispatch_cache
    from .ops import fusion_cycle as _fusion_cycle
    # Queued async collectives land BEFORE teardown (every submitted op
    # eventually executes — the reference drains its tensor queue in
    # ShutDownHorovod the same way); the cycle timer stops with the world.
    if _state is not None:
        try:
            _fusion_cycle.drain()
        except Exception:
            hvd_logging.exception("fusion-cycle drain failed at shutdown")
    _engine_service.reset_service()
    _autotune.reset()
    # Plans hold compiled programs over this world's meshes; none survive
    # a shutdown (the generation epoch also guards re-init races).
    _dispatch_cache.invalidate("runtime shutdown")
    # Conformance trace out LAST — the teardown above records events
    # too (service stop, plan shelving); the recorder then resets so a
    # later init() starts a fresh trace incarnation.
    _conformance.maybe_dump("shutdown")
    _conformance.reset()
    if _bootstrap_kv_server is not None:
        try:
            _bootstrap_kv_server.stop()
        except Exception as e:
            hvd_logging.debug("bootstrap KV server stop failed: %s", e)
        _bootstrap_kv_server = None
    if _bootstrap_seeded_env:
        # the seeded coordinates point at the server just stopped; a later
        # init() must bootstrap afresh, not trust stale env
        for var in ("HVD_KV_ADDR", "HVD_KV_PORT", "HVD_SECRET_KEY"):
            os.environ.pop(var, None)
        _bootstrap_seeded_env = False
    with _lock:
        _state = None


def _loopback_shutdown(ctx) -> None:
    """``shutdown()`` on a loopback rank thread: drain this rank's
    queued async work, stop its negotiation services, drop its dispatch
    plans — the per-rank mirror of the process-wide teardown. Shared
    process state (autotune, timeline, the OTHER ranks' worlds) is
    untouched."""
    if ctx.runtime_state is None:
        return
    from . import conformance as _conformance
    from . import engine_service as _engine_service
    from .ops import dispatch_cache as _dispatch_cache
    from .ops import fusion_cycle as _fusion_cycle
    try:
        _fusion_cycle.drain()
    except Exception:
        hvd_logging.exception(
            "loopback fusion-cycle drain failed at shutdown")
    # Elastic warm re-form (docs/elastic.md): park this rank's restorable
    # plans on the shape-keyed shelf BEFORE the service reset invalidates
    # the store — a later re-form back to this shape grafts their
    # compiled stages instead of re-tracing. No-op under HVD_ELASTIC_WARM=0.
    shelved = _dispatch_cache.shelve_for_reform()
    if shelved:
        hvd_logging.debug("loopback shutdown: shelved %d dispatch plans",
                          shelved)
    _engine_service.reset_service()
    _dispatch_cache.invalidate("loopback runtime shutdown")
    sched, ctx.scheduler = ctx.scheduler, None
    if sched is not None:
        sched.stop()
    # Per-rank conformance trace out LAST — the teardown above records
    # events too (plan shelving, service stop); reset so an elastic
    # re-init in the SAME context starts a fresh trace (the generation
    # in the file name keeps incarnations apart).
    _conformance.maybe_dump("shutdown")
    _conformance.reset()
    # NOTE: ctx.notification_manager deliberately survives — an elastic
    # re-init calls this mid-run and the manager's listeners must carry
    # into the next round (real elastic parity); the worker wrapper and
    # _abrupt_stop shut it down when the rank truly ends.
    ctx.runtime_state = None


def _current_state() -> _RuntimeState | None:
    ctx = _lbctx.current()
    if ctx is not None:
        return ctx.runtime_state
    return _state


def is_initialized() -> bool:
    return _current_state() is not None


def generation() -> int:
    """Monotonic init() counter (see ProcessSet.mesh cache). Loopback
    rank threads count their own context's init()s."""
    ctx = _lbctx.current()
    if ctx is not None:
        return ctx.generation
    return _generation


def _get() -> _RuntimeState:
    st = _current_state()
    if st is None:
        raise NotInitializedError(
            "horovod_tpu has not been initialized; call hvd.init() first.")
    return st


# --- rank/size queries (reference C API: operations.cc:944-1030) ----------

def size() -> int:
    """Total number of chips (== Horovod world size when 1 GPU per process)."""
    return len(_get().devices)


def local_size() -> int:
    """Chips driven by this controller process."""
    return len(_get().local_ranks)


def rank() -> int:
    """Representative global rank of this process: its first local chip.

    Under SPMD one process drives many chips; inside traced code use
    :func:`axis_rank` for the per-chip rank.
    """
    st = _get()
    return st.local_ranks[0] if st.local_ranks else 0


def local_rank() -> int:
    # The representative rank (first local chip) is by definition local
    # index 0 within this process.
    _get()
    return 0


def cross_rank() -> int:
    """Host index (reference cross-communicator rank, ``common.h:166-170``)."""
    return _get().process_index


def cross_size() -> int:
    return _get().process_count


def process_rank() -> int:
    return _get().process_index


def process_count() -> int:
    return _get().process_count


def is_homogeneous() -> bool:
    """True when every process drives the same number of chips
    (reference ``horovod_is_homogeneous``, ``operations.cc:1013-1017``)."""
    st = _get()
    counts = {}
    if st.rank_process_map is not None:
        for p in st.rank_process_map:
            counts[p] = counts.get(p, 0) + 1
    else:
        for d in st.devices:
            counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1


def mesh() -> Mesh:
    """The global 1-D rank mesh."""
    return _get().mesh


def axis_name() -> str:
    return _get().axis_name


def devices() -> list:
    return list(_get().devices)


def process_set_table():
    return _get().process_set_table


def local_ranks() -> list:
    return list(_get().local_ranks)


def process_of_rank(global_rank: int) -> int:
    """Index of the process owning chip ``global_rank`` (devices are
    rank-ordered process-major; loopback worlds carry the virtual
    mapping explicitly — their fake devices all report process 0)."""
    st = _get()
    if st.rank_process_map is not None:
        return st.rank_process_map[global_rank]
    return st.devices[global_rank].process_index


# ---------------------------------------------------------------------------
# capability queries (reference basics.py:273-371) — migration shims so
# `if hvd.nccl_built(): ...` style feature probes run unmodified. The
# rebuild has exactly one collective backend: XLA over ICI/DCN.
# ---------------------------------------------------------------------------

def xla_built() -> bool:
    """True: XLA collectives are the (only) backend of the rebuild."""
    return True


def xla_enabled() -> bool:
    return True


def tpu_built() -> bool:
    """Whether a TPU backend is live (or configured) in this process.

    Safe to call before :func:`init`, like the reference's ``*_built()``
    probes: before the runtime is up this answers from configuration only
    — touching ``jax.default_backend()`` here would initialize the XLA
    client and break the later ``jax.distributed.initialize`` (see
    ``_maybe_distributed_init``)."""
    import jax

    if is_initialized():
        try:
            return jax.default_backend() == "tpu"
        except Exception:
            return False
    platforms = (os.environ.get("JAX_PLATFORMS")
                 or getattr(jax.config, "jax_platforms", None) or "")
    return "tpu" in str(platforms).lower()


def mpi_threads_supported() -> bool:
    """Reference ``hvd.mpi_threads_supported()``. The rebuild has no MPI;
    the analogous guarantee — collectives may be driven from multiple
    Python threads — holds (the engine service thread does exactly that),
    so answer True like a threads-enabled MPI build would."""
    return True


def mpi_enabled() -> bool:
    """False: no MPI backend — XLA collectives replace it (SURVEY §5.8)."""
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    """False: the launcher's HTTP-KV rendezvous plays gloo's role."""
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    """False: ICI/DCN collectives are emitted by XLA, not NCCL."""
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False
