"""Environment-variable knob surface.

TPU-native rebuild of the reference's env config system (knob list at
``/root/reference/horovod/common/common.h:107-140``, parsed in
``/root/reference/horovod/common/utils/env_parser.cc`` and
``BackgroundThreadLoop`` at ``/root/reference/horovod/common/operations.cc:436-607``).

All knobs use the ``HVD_`` prefix; the reference's ``HOROVOD_`` spellings are
accepted as fallbacks so existing user scripts keep working.
"""

from __future__ import annotations

import os

from ..loopback import context as _lbctx

# --- knob names (HVD_*; HOROVOD_* accepted as fallback) -------------------
FUSION_THRESHOLD = "FUSION_THRESHOLD"  # bytes; reference default 128 MB (operations.cc:491-496)
TRACED_FUSION_THRESHOLD = "TRACED_FUSION_THRESHOLD"  # bytes; 0 (default) = let XLA's combiner fuse traced collectives
CYCLE_TIME = "CYCLE_TIME"  # ms; reference default 1 ms (operations.cc:499-506)
CACHE_CAPACITY = "CACHE_CAPACITY"  # reference default 1024 (global_state.h:89)
TIMELINE = "TIMELINE"  # trace output path (operations.cc:466-488)
TIMELINE_MARK_CYCLES = "TIMELINE_MARK_CYCLES"
AUTOTUNE = "AUTOTUNE"
AUTOTUNE_STRATEGY = "AUTOTUNE_STRATEGY"  # coordinate (default) | bayesian
AUTOTUNE_LOG = "AUTOTUNE_LOG"
AUTOTUNE_WARMUP_SAMPLES = "AUTOTUNE_WARMUP_SAMPLES"
AUTOTUNE_STEPS_PER_SAMPLE = "AUTOTUNE_STEPS_PER_SAMPLE"
AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
LOG_LEVEL = "LOG_LEVEL"
LOG_TIMESTAMP = "LOG_TIMESTAMP"
STALL_CHECK_DISABLE = "STALL_CHECK_DISABLE"
STALL_CHECK_TIME_SECONDS = "STALL_CHECK_TIME_SECONDS"  # reference warns at 60 s (stall_inspector.h:78)
STALL_SHUTDOWN_TIME_SECONDS = "STALL_SHUTDOWN_TIME_SECONDS"
HIERARCHICAL_ALLREDUCE = "HIERARCHICAL_ALLREDUCE"
HIERARCHICAL_ALLGATHER = "HIERARCHICAL_ALLGATHER"
HIERARCHICAL_ICI_SIZE = "HIERARCHICAL_ICI_SIZE"  # chips per ICI island; default local_size
MESH_AXES = "MESH_AXES"  # composed-mesh model-axis carve, e.g. "seq:2" or "expert:4,stage:2" (parallel/mesh.py)
# (the reference's HOROVOD_BATCH_D2D_MEMCOPIES has no knob here by
# design: XLA fuses small copies into the compiled program, so there is
# nothing runtime-batchable to toggle)
ADAPTIVE_CYCLE = "ADAPTIVE_CYCLE"  # event-driven negotiation tick (default on)
PENDING_CYCLE_TIME = "PENDING_CYCLE_TIME"  # ms; cycle floor while work is in flight
FUSION_MAX_PENDING = "FUSION_MAX_PENDING"  # bytes; fusion-cycle backpressure cap (default 4x FUSION_THRESHOLD)
MAX_INFLIGHT_FLUSHES = "MAX_INFLIGHT_FLUSHES"  # pipelined flush executor slots (0/1 = synchronous)
PIPELINE_THRESHOLD = "PIPELINE_THRESHOLD"  # bytes; fused wire buffers past this split into chunks
PIPELINE_CHUNKS = "PIPELINE_CHUNKS"  # chunk count for the large-buffer software pipeline
PIPELINE_PINGPONG = "PIPELINE_PINGPONG"  # auto|1|0: recycle wire buffers across flushes via donation
DYNAMIC_PROCESS_SETS = "DYNAMIC_PROCESS_SETS"
DYNAMIC_ENGINE = "DYNAMIC_ENGINE"  # 0 disables multi-process negotiation
ELASTIC_TIMEOUT = "ELASTIC_TIMEOUT"
ELASTIC_GRACE = "ELASTIC_GRACE"  # s a slot-removed worker gets to exit cleanly (0 = immediate kill)
ELASTIC_WARM = "ELASTIC_WARM"  # auto|1|0: shape-keyed cache survival across elastic re-forms
AUTOSCALE = "AUTOSCALE"  # closed-loop elastic autoscaling policy (0 = scripted/manual churn only)
AUTOSCALE_SLO_MS = "AUTOSCALE_SLO_MS"  # step-time SLO target; 0 = breach/idle rules off (evict-only)
AUTOSCALE_INTERVAL = "AUTOSCALE_INTERVAL"  # s per policy evaluation window
AUTOSCALE_BREACH_WINDOWS = "AUTOSCALE_BREACH_WINDOWS"  # consecutive SLO-breach windows before scale-up
AUTOSCALE_IDLE_WINDOWS = "AUTOSCALE_IDLE_WINDOWS"  # consecutive idle windows before graceful scale-down
AUTOSCALE_EVICT_WINDOWS = "AUTOSCALE_EVICT_WINDOWS"  # consecutive windows blaming one straggler before eviction
AUTOSCALE_COOLDOWN = "AUTOSCALE_COOLDOWN"  # s after any membership decision before the next may fire
AUTOSCALE_MIN = "AUTOSCALE_MIN"  # world floor the policy never shrinks below (default: driver min_np)
AUTOSCALE_MAX = "AUTOSCALE_MAX"  # world ceiling the policy never grows past (default: driver max_np)
AUTOSCALE_GRACE = "AUTOSCALE_GRACE"  # s of slot-lost grace a policy departure (scale-down/evict) gets
AUTOSCALE_IDLE_FACTOR = "AUTOSCALE_IDLE_FACTOR"  # fraction of the SLO below which a window counts as idle
GLOO_TIMEOUT_SECONDS = "GLOO_TIMEOUT_SECONDS"  # KV transport op timeout
SPARSE_AS_DENSE = "SPARSE_AS_DENSE"  # force sparse grads onto dense allreduce
BUCKET_BYTES = "BUCKET_BYTES"  # gradient bucket size for backward-pass overlap (0 = whole-tree)
EAGER_CHAIN = "EAGER_CHAIN"  # auto|1|0: let eager consumer math chain on in-flight collective results
STEP_CAPTURE = "STEP_CAPTURE"  # capture-and-replay of the per-step collective stream (0 = off)
GSPMD_CACHE = "GSPMD_CACHE"  # cached-program fast path for jit/pjit train steps (0 = plain jit per call)
GSPMD_CACHE_DONATE = "GSPMD_CACHE_DONATE"  # auto|1|0: donate param/opt-state buffers into cached GSPMD steps
FLASH_ATTENTION = "FLASH_ATTENTION"  # ring / Ulysses: opt into the Pallas kernels
DEBUG_INVARIANTS = "DEBUG_INVARIANTS"  # dev-mode runtime invariant checker
SCHED_CHECK = "SCHED_CHECK"  # cooperative schedule-exploration checker (tools/hvdsched)
SCHED_SEED = "SCHED_SEED"  # base PRNG seed for hvdsched schedule choices
SCHED_SCHEDULES = "SCHED_SCHEDULES"  # schedule budget per hvdsched exploration
SPARK_START_TIMEOUT = "SPARK_START_TIMEOUT"  # spark barrier-task scheduling bound
START_TIMEOUT = "START_TIMEOUT"  # programmatic run() worker startup bound
FAULT_SPEC = "FAULT_SPEC"  # deterministic fault-injection spec (tests/chaos)
HEALTH_INTERVAL = "HEALTH_INTERVAL"  # s between liveness beats (0 = watchdog off)
HEALTH_TIMEOUT = "HEALTH_TIMEOUT"  # s without a peer beat before it is declared dead
RETRY_MAX_ATTEMPTS = "RETRY_MAX_ATTEMPTS"  # attempts per retried RPC/KV call
RETRY_BACKOFF_MS = "RETRY_BACKOFF_MS"  # initial backoff between attempts
RETRY_MAX_BACKOFF_MS = "RETRY_MAX_BACKOFF_MS"  # backoff growth cap
RETRY_JITTER = "RETRY_JITTER"  # +/- fraction of deterministic jitter on backoff
LOOPBACK = "LOOPBACK"  # "1" in loopback rank threads (hvd.loopback.world)
LOOPBACK_TIMEOUT = "LOOPBACK_TIMEOUT"  # s per loopback collective rendezvous (default scales with world)
RESPONSE_CACHE = "RESPONSE_CACHE"  # coordinator ResponseCache: auto = on when hierarchy active, 0 off, 1 on, >1 = capacity
NEGOTIATION_GROUP_SIZE = "NEGOTIATION_GROUP_SIZE"  # ranks per leader group in the hierarchical control plane
HIER_NEGOTIATION = "HIER_NEGOTIATION"  # auto|1|0: two-level leader/member negotiation exchange
METRICS = "METRICS"  # unified metrics registry (0 = hot instruments off)
METRICS_PORT = "METRICS_PORT"  # base port for the per-worker /metrics server
STRAGGLER_THRESHOLD = "STRAGGLER_THRESHOLD"  # s of submit lag naming a rank a straggler
QOS = "QOS"  # multi-tenant QoS collective engine (0 = legacy single-tenant FIFO)
QOS_WINDOW = "QOS_WINDOW"  # arbitration window: parked batches before a pump grants
QOS_QUANTUM = "QOS_QUANTUM"  # DRR quantum bytes credited per weight unit per round
QOS_STARVE_LIMIT = "QOS_STARVE_LIMIT"  # grants between forced oldest-first grants (0 = off)
QOS_DEFAULT_PRIORITY = "QOS_DEFAULT_PRIORITY"  # tier for unconfigured tenants
QOS_DEFAULT_WEIGHT = "QOS_DEFAULT_WEIGHT"  # DRR weight for unconfigured tenants
QOS_PENDING_QUOTA = "QOS_PENDING_QUOTA"  # default per-tenant pending-bytes quota (0 = unlimited)
QOS_SHED_POLICY = "QOS_SHED_POLICY"  # quota policy for unconfigured tenants: block | shed
QOS_CLASSES = "QOS_CLASSES"  # per-tenant class spec string (docs/qos.md grammar)
CONFORMANCE = "CONFORMANCE"  # cross-rank lockstep conformance recorder (0 = off)
CONFORMANCE_DIR = "CONFORMANCE_DIR"  # per-rank trace dump directory (empty = dump on demand only)
CONFORMANCE_RING = "CONFORMANCE_RING"  # full-payload ring capacity per rank recorder
CKPT_DIR = "CKPT_DIR"  # sharded async snapshot directory (empty = state plane off)
CKPT_INTERVAL = "CKPT_INTERVAL"  # commits between background snapshots
CKPT_PEER_RESTORE = "CKPT_PEER_RESTORE"  # re-form state re-sync from survivor shards (0 = rank-0 broadcast)
CKPT_SHARD_QUORUM = "CKPT_SHARD_QUORUM"  # min survivors holding a consistent manifest before peer-restore runs

# rendezvous / launcher env seeded by `hvdrun` (reference:
# HOROVOD_RANK/SIZE/LOCAL_RANK... seeded at gloo_run.py:65-101,201-226)
RANK = "RANK"
SIZE = "SIZE"
LOCAL_RANK = "LOCAL_RANK"
LOCAL_SIZE = "LOCAL_SIZE"
CROSS_RANK = "CROSS_RANK"
CROSS_SIZE = "CROSS_SIZE"
COORDINATOR_ADDR = "COORDINATOR_ADDR"
COORDINATOR_PORT = "COORDINATOR_PORT"
NUM_PROCESSES = "NUM_PROCESSES"
PROCESS_ID = "PROCESS_ID"
KV_ADDR = "KV_ADDR"
KV_PORT = "KV_PORT"
SECRET_KEY = "SECRET_KEY"
HOSTNAME = "HOSTNAME"
ELASTIC = "ELASTIC"  # "1" in workers launched by an elastic driver
ELASTIC_ROUND = "ELASTIC_ROUND"  # round a worker was spawned into (seeded)

_PREFIXES = ("HVD_", "HOROVOD_")

# Runtime knob overrides (autotuner). The reference's ParameterManager
# mutates the live knob values in HorovodGlobalState while env-set knobs
# stay fixed (``operations.cc:490-523``); here overrides sit *under* the
# environment: an env-set knob always wins (it is "fixed"), and consumers
# that read knobs through this module pick up tuned values transparently.
_overrides: dict[str, str] = {}

# Bumped on every override mutation. Consumers that cache derived state
# (the dispatch plan cache keys fusion layouts and hierarchical routing off
# knob values) compare epochs instead of re-reading every knob per call.
_override_epoch = 0


def override_epoch() -> int:
    """Monotonic counter of override mutations (see ``_override_epoch``)."""
    return _override_epoch


def set_override(name: str, value) -> None:
    """Install a runtime override for knob ``name`` (autotuner)."""
    global _override_epoch
    value = str(value)
    if _overrides.get(name) == value:
        return  # no-op re-apply (every autotune sample re-applies the
        # whole state) must not bump the epoch and flush dispatch plans
    _overrides[name] = value
    # epoch, not telemetry: keys dispatch-plan invalidation
    _override_epoch += 1  # hvdlint: disable=metrics-registry


def clear_override(name: str) -> None:
    global _override_epoch
    if _overrides.pop(name, None) is not None:
        _override_epoch += 1  # hvdlint: disable=metrics-registry


def clear_overrides() -> None:
    global _override_epoch
    if _overrides:
        _override_epoch += 1  # hvdlint: disable=metrics-registry
    _overrides.clear()


def _overlay() -> dict | None:
    """The loopback rank context's per-thread env overlay (the launcher
    contract for rank THREADS — ``os.environ`` is shared by every rank
    in one interpreter, so per-rank values live here). None outside a
    loopback context."""
    ctx = _lbctx.current()
    return ctx.env if ctx is not None else None


def is_env_fixed(name: str) -> bool:
    """True when the user pinned this knob via the environment — the
    autotuner must treat it as untunable (reference ``SetAutoTuning`` /
    fixed params, ``operations.cc:490-523``). A loopback overlay entry
    counts: it is that rank's environment."""
    ov = _overlay()
    if ov is not None and any((p + name) in ov for p in _PREFIXES):
        return True
    return any(os.environ.get(p + name) is not None for p in _PREFIXES)


def get(name: str, default: str | None = None) -> str | None:
    """Look up knob ``name``: the loopback rank overlay (when on a rank
    thread), then the environment (HVD_/HOROVOD_ prefixes), then runtime
    overrides, then ``default``."""
    ov = _overlay()
    if ov is not None:
        for prefix in _PREFIXES:  # both spellings, like the environ path
            val = ov.get(prefix + name)
            if val is not None:
                return val
    for prefix in _PREFIXES:
        val = os.environ.get(prefix + name)
        if val is not None:
            return val
    val = _overrides.get(name)
    if val is not None:
        return val
    return default


def require(name: str) -> str:
    """Look up knob ``name`` like :func:`get`, but raise when it is absent
    — for the launcher-seeded worker contract (``HVD_RANK``/``HVD_KV_*``),
    where a missing variable means the process was not started by a
    launcher and continuing would only fail more confusingly later."""
    val = get(name)
    if val is None:
        raise RuntimeError(
            f"required environment variable HVD_{name} is not set (workers "
            "expect the launcher-seeded rendezvous contract; see "
            "docs/knobs.md)")
    return val


def set_env(name: str, value, *, only_if_unset: bool = False) -> None:
    """Seed knob ``name`` into the process environment under the ``HVD_``
    prefix (the launcher/bootstrap side of the contract). Writing through
    the registry keeps the knob inventory centralized; ``only_if_unset``
    preserves an existing HVD_/HOROVOD_ spelling (``setdefault``)."""
    ov = _overlay()
    if ov is not None:
        # On a loopback rank thread the write is rank-local: it must
        # never leak into the interpreter-wide environment the other
        # ranks (and the main thread) read.
        if only_if_unset and (any((p + name) in ov for p in _PREFIXES)
                              or any(os.environ.get(p + name) is not None
                                     for p in _PREFIXES)):
            return
        ov["HVD_" + name] = str(value)
        return
    if only_if_unset and any(
            os.environ.get(p + name) is not None for p in _PREFIXES):
        return
    os.environ["HVD_" + name] = str(value)


def get_bool(name: str, default: bool = False) -> bool:
    val = get(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def get_int(name: str, default: int) -> int:
    val = get(name)
    if val is None:
        return default
    try:
        return int(val)
    except ValueError:
        return default


def get_float(name: str, default: float) -> float:
    val = get(name)
    if val is None:
        return default
    try:
        return float(val)
    except ValueError:
        return default


# Defaults mirrored from the reference (operations.cc:491-506, global_state.h:89).
DEFAULT_FUSION_THRESHOLD_BYTES = 128 * 1024 * 1024
DEFAULT_CYCLE_TIME_MS = 1.0
DEFAULT_CACHE_CAPACITY = 1024
DEFAULT_STALL_WARNING_SECONDS = 60.0


def fusion_threshold_bytes() -> int:
    return get_int(FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES)


def cycle_time_ms() -> float:
    return get_float(CYCLE_TIME, DEFAULT_CYCLE_TIME_MS)


def cache_capacity() -> int:
    return get_int(CACHE_CAPACITY, DEFAULT_CACHE_CAPACITY)


# Pipelined flush executor defaults. Two in-flight slots are the classic
# double-buffering point: flush k+1's host-side fuse/negotiation overlaps
# flush k's device collective without unbounded device-queue growth. The
# 4 MiB / 4-chunk pipeline splits a large fused wire buffer into chunk
# programs so the collective of chunk i overlaps the fuse/split (and, on
# the CPU mesh, the per-device execution) of its neighbors.
DEFAULT_MAX_INFLIGHT_FLUSHES = 2
DEFAULT_PIPELINE_THRESHOLD_BYTES = 4 * 1024 * 1024
DEFAULT_PIPELINE_CHUNKS = 4


def max_inflight_flushes() -> int:
    return get_int(MAX_INFLIGHT_FLUSHES, DEFAULT_MAX_INFLIGHT_FLUSHES)


def pipeline_enabled() -> bool:
    """The pipelined flush executor is engaged at >= 2 slots; 0/1 keep the
    synchronous (execute-on-the-triggering-thread) behavior byte-for-byte."""
    return max_inflight_flushes() >= 2


def pipeline_threshold_bytes() -> int:
    return get_int(PIPELINE_THRESHOLD, DEFAULT_PIPELINE_THRESHOLD_BYTES)


def pipeline_chunks() -> int:
    return get_int(PIPELINE_CHUNKS, DEFAULT_PIPELINE_CHUNKS)


# Gradient bucketing (optim/_bucketed_allreduce): the backward pass's
# dense gradient pytree is partitioned into size-bounded buckets, each
# issued as its own async grouped allreduce so bucket k's collective is
# in flight while bucket k+1 fuses host-side. 64 MiB matches the
# reference's fusion-buffer sweet spot (half the 128 MB threshold: big
# enough to amortize dispatch, small enough that several buckets pipeline
# through the executor's slots). 0 = whole-tree single grouped call.
DEFAULT_BUCKET_BYTES = 64 * 1024 * 1024


def bucket_bytes() -> int:
    return get_int(BUCKET_BYTES, DEFAULT_BUCKET_BYTES)


def step_capture_enabled() -> bool:
    """Step capture-and-replay (``ops/step_capture.py``): record the
    marked step's rank-deterministic flush stream once, then replay the
    whole step's collective work as ONE cached jitted program. Off by
    default — the eager per-flush path is the reference behavior; the
    capture plan invalidates transparently on any stream divergence.
    Mutually exclusive with the multi-tenant QoS engine: capture assumes
    ONE repeating single-tenant flush stream, while QoS interleaves
    tenants' flushes by admission policy — with ``HVD_QOS=1`` capture
    stays off (the transparent eager path, like any divergence;
    docs/qos.md)."""
    return get_bool(STEP_CAPTURE, False) and not qos_enabled()


def gspmd_cache_enabled() -> bool:
    """GSPMD cached-program fast path (``ops/gspmd_cache.py``): store
    lowered+compiled jit/pjit step executables in the dispatch plan
    cache under a stable step signature, so re-created step closures
    replay instead of retracing. Default on — ``hvd.cached_step`` is an
    explicit opt-in API, so the knob is a kill switch; it also rides
    the cache-wide ``HVD_CACHE_CAPACITY=0`` off switch (cached steps
    are dispatch plans like any other)."""
    return get_bool(GSPMD_CACHE, True) and cache_capacity() > 0


def gspmd_donate_enabled(platform: str) -> bool:
    """Whether cached GSPMD steps donate their parameter/optimizer
    buffers (``donate_argnums`` derived from the step's pytree layout).
    'auto' follows :func:`donation_effective`: on backends where
    donation is a memory no-op the derivation (an extra abstract trace)
    buys nothing."""
    val = (get(GSPMD_CACHE_DONATE, "auto") or "auto").strip().lower()
    if val in ("1", "true", "yes", "on"):
        return True
    if val in ("0", "false", "no", "off"):
        return False
    return donation_effective(platform)


def pipeline_chunking_enabled() -> bool:
    """Large-buffer chunk pipelining rides the pipelined executor: it is
    part of the same overlap mechanism, and disabling the executor
    (MAX_INFLIGHT_FLUSHES<=1) must restore the exact pre-pipeline
    program compositions."""
    return (pipeline_enabled() and pipeline_threshold_bytes() > 0
            and pipeline_chunks() >= 2)


# Failure-domain defaults (docs/robustness.md). The health timeout must sit
# far below the 600 s exchange deadline — a dead peer should surface as a
# PeerFailureError in seconds, not after the full negotiation budget. The
# retry ladder (50 ms * 2^k capped at 2 s, 5 attempts) absorbs single-digit
# seconds of KV/coordinator flap without masking a real outage.
DEFAULT_HEALTH_INTERVAL_S = 2.0
DEFAULT_HEALTH_TIMEOUT_S = 30.0
DEFAULT_RETRY_MAX_ATTEMPTS = 5
DEFAULT_RETRY_BACKOFF_MS = 50.0
DEFAULT_RETRY_MAX_BACKOFF_MS = 2000.0
DEFAULT_RETRY_JITTER = 0.25


def health_interval_s() -> float:
    return get_float(HEALTH_INTERVAL, DEFAULT_HEALTH_INTERVAL_S)


def health_timeout_s() -> float:
    return get_float(HEALTH_TIMEOUT, DEFAULT_HEALTH_TIMEOUT_S)


# Straggler attribution (health.StragglerTracker, docs/metrics.md): a rank
# whose negotiation frame reaches the KV server this many seconds after
# the round's first submitter is counted a straggler for that round. 1 s
# sits far above loopback/LAN submit jitter (single-digit ms) and far
# below the health timeout — sustained straggling warns long before a
# rank looks dead.
DEFAULT_STRAGGLER_THRESHOLD_S = 1.0


def straggler_threshold_s() -> float:
    return get_float(STRAGGLER_THRESHOLD, DEFAULT_STRAGGLER_THRESHOLD_S)


# Multi-tenant QoS defaults (horovod_tpu/qos.py, docs/qos.md). The
# 4-batch arbitration window keeps the gate's deterministic reordering
# span small (latency) while letting strict-priority/DRR ordering bite
# on a backlog; the 64 KiB quantum approximates one small fused flush,
# so weights translate into byte shares at flush granularity; the
# 16-grant starvation valve bounds how long strict priority can hold a
# low-tier batch (deterministic grant-count aging, never wall-clock —
# wall-clock aging would break the rank-deterministic grant order).
DEFAULT_QOS_WINDOW = 4
DEFAULT_QOS_QUANTUM = 64 * 1024
DEFAULT_QOS_STARVE_LIMIT = 16
DEFAULT_QOS_WEIGHT = 1.0


# Conformance recorder defaults (horovod_tpu/conformance.py,
# docs/conformance.md). The 256-event payload ring bounds per-rank
# memory while keeping the recent window a divergence report needs —
# the compact per-event digest chain localizes ANY event; the ring only
# decides whether its full payload is still quotable.
DEFAULT_CONFORMANCE_RING = 256


def conformance_enabled() -> bool:
    """Cross-rank lockstep conformance recorder
    (``horovod_tpu/conformance.py``): off by default — every decision
    point's hook is then one cached module-bool check and an early
    return (the ``utils/faults.py`` fast-path idiom)."""
    return get_bool(CONFORMANCE, False)


def conformance_dir() -> str:
    """``HVD_CONFORMANCE_DIR``: directory for per-rank trace dumps at
    shutdown/abort. Empty (default) = traces stay in memory and are
    only materialized by an explicit ``hvd.conformance_dump()``."""
    return (get(CONFORMANCE_DIR, "") or "").strip()


def conformance_ring() -> int:
    return max(0, get_int(CONFORMANCE_RING, DEFAULT_CONFORMANCE_RING))


# Checkpoint state plane defaults (horovod_tpu/checkpoint.py,
# docs/checkpoint.md). Snapshotting every commit would put a host-side
# pickle+write on every step's critical path shadow; every 10th commit
# keeps the restore point seconds-fresh at commit-per-step cadence while
# the background thread stays comfortably ahead. Peer-restore defaults
# ON unconditionally — it serves from the survivors' LIVE committed
# trees (no snapshot directory required) and the degraded rank-0
# broadcast stays available as the typed fallback, so the fast path is
# safe to prefer. Quorum 1 admits the smallest useful survivor set; jobs
# that fear a lone corrupted survivor raise it.
DEFAULT_CKPT_INTERVAL = 10
DEFAULT_CKPT_SHARD_QUORUM = 1


def ckpt_dir() -> str:
    """``HVD_CKPT_DIR``: root directory for sharded background
    snapshots (``horovod_tpu/checkpoint.py`` state plane). Empty
    (default) = the state plane is off and elastic re-forms re-sync via
    the rank-0 broadcast only."""
    return (get(CKPT_DIR, "") or "").strip()


def ckpt_interval() -> int:
    return max(1, get_int(CKPT_INTERVAL, DEFAULT_CKPT_INTERVAL))


def ckpt_peer_restore_enabled() -> bool:
    """Whether a re-formed world re-syncs model state by pulling shards
    from survivors instead of the rank-0 full-tree broadcast. Only
    meaningful when survivors exist; the degraded broadcast path always
    remains the fallback."""
    return get_bool(CKPT_PEER_RESTORE, True)


def ckpt_shard_quorum() -> int:
    return max(1, get_int(CKPT_SHARD_QUORUM, DEFAULT_CKPT_SHARD_QUORUM))


def qos_enabled() -> bool:
    """Multi-tenant QoS collective engine (``horovod_tpu/qos.py``): off
    by default — ``HVD_QOS=0`` keeps the single-tenant FIFO flush
    pipeline byte-for-byte."""
    return get_bool(QOS, False)


def qos_window() -> int:
    return get_int(QOS_WINDOW, DEFAULT_QOS_WINDOW)


def qos_quantum_bytes() -> int:
    return get_int(QOS_QUANTUM, DEFAULT_QOS_QUANTUM)


def qos_starve_limit() -> int:
    return get_int(QOS_STARVE_LIMIT, DEFAULT_QOS_STARVE_LIMIT)


def mesh_axes() -> str:
    """``HVD_MESH_AXES``: the composed-mesh model-axis carve
    (``parallel/mesh.py``), a comma list of ``name:size`` pairs carved
    out of the ICI island — e.g. ``"seq:2"`` or ``"expert:4,stage:2"``.
    Empty (default) = no model axes: the pure data-parallel
    ``dcn × ici_dp`` layout."""
    return (get(MESH_AXES, "") or "").strip()


# Hierarchical negotiation control plane (horovod_tpu/negotiation/,
# docs/negotiation.md). Group size 8 mirrors the data path's ICI-island
# default (ops/hierarchical.py): one leader per "island" runs the
# cross-leader exchange while members pay O(1) KV ops per round. The
# coordinator ResponseCache defaults to AUTO: on (default capacity)
# whenever the hierarchical control plane is active for the world —
# those are the worlds where steady-state batches already serve with
# zero KV rounds and the cache's divergence-surfacing tradeoff (a
# diverged rank times out instead of every rank seeing the mismatch
# error) is paid for by a typed join-race error + invalidation
# telemetry (docs/troubleshooting.md). Flat small worlds stay off, and
# ``HVD_RESPONSE_CACHE=0`` is a hard off.
DEFAULT_NEGOTIATION_GROUP_SIZE = 8
DEFAULT_RESPONSE_CACHE_CAPACITY = 1024


def negotiation_group_size() -> int:
    return max(1, get_int(NEGOTIATION_GROUP_SIZE,
                          DEFAULT_NEGOTIATION_GROUP_SIZE))


def response_cache_capacity(world_size: int | None = None) -> int:
    """``HVD_RESPONSE_CACHE``: ``auto`` (default) = on at the default
    capacity when hierarchical negotiation is active for ``world_size``
    (else off; ``None`` — callers without a world — reads as off);
    ``0`` = hard off; ``1`` = on at the default capacity; any larger
    value = on with that many entries."""
    raw = (get(RESPONSE_CACHE, "auto") or "auto").strip().lower()
    if raw in ("auto", ""):
        if world_size is not None and hier_negotiation_enabled(world_size):
            return DEFAULT_RESPONSE_CACHE_CAPACITY
        return 0
    try:
        v = int(raw)
    except ValueError:
        v = 0
    if v <= 0:
        return 0
    return DEFAULT_RESPONSE_CACHE_CAPACITY if v == 1 else v


def hier_negotiation_enabled(world_size: int) -> bool:
    """Whether the two-level (leader/member) negotiation exchange runs
    for a service of ``world_size`` members. ``auto`` (default) engages
    it only when the world is larger than one leader group — small
    worlds keep today's flat protocol byte-for-byte."""
    val = (get(HIER_NEGOTIATION, "auto") or "auto").strip().lower()
    if val in ("1", "true", "yes", "on"):
        return world_size > 1
    if val in ("0", "false", "no", "off"):
        return False
    return world_size > negotiation_group_size()


# Closed-loop elastic autoscaling (elastic/policy.py, docs/elastic.md).
# The 2 s evaluation window matches the health-beat default: membership
# decisions ride the same "seconds, not negotiation deadlines" cadence.
# Hysteresis defaults are asymmetric on purpose — growing is cheap and
# reversible (3 breach windows), shrinking throws capacity away (5 idle
# windows), and eviction replaces a live-but-slow worker (3 blame
# windows, the StragglerTracker's own sustain default). The 15 s
# cooldown spans a loopback re-form plus settle time, so one decision's
# own disruption can never read as the next window's signal (the
# oscillation bound tested by the adversarial flapping load).
DEFAULT_AUTOSCALE_INTERVAL_S = 2.0
DEFAULT_AUTOSCALE_BREACH_WINDOWS = 3
DEFAULT_AUTOSCALE_IDLE_WINDOWS = 5
DEFAULT_AUTOSCALE_EVICT_WINDOWS = 3
DEFAULT_AUTOSCALE_COOLDOWN_S = 15.0
DEFAULT_AUTOSCALE_GRACE_S = 30.0
DEFAULT_AUTOSCALE_IDLE_FACTOR = 0.5


def autoscale_enabled() -> bool:
    """Closed-loop autoscaling (``elastic/policy.py``): the driver-side
    policy decides ``add``/``remove``/``evict`` from the metrics-registry
    sensors instead of a script. Off by default — scripted churn and
    manual discovery stay the only membership sources."""
    return get_bool(AUTOSCALE, False)


def autoscale_slo_s() -> float:
    """Step-time SLO target in SECONDS (knob is ms). 0 disables the
    breach/idle rules — the policy then only evicts stragglers."""
    return get_float(AUTOSCALE_SLO_MS, 0.0) / 1e3


def autoscale_interval_s() -> float:
    return get_float(AUTOSCALE_INTERVAL, DEFAULT_AUTOSCALE_INTERVAL_S)


# Elastic warm re-form (docs/elastic.md): plan stores / step plans /
# coordinator response-cache entries are keyed by process-set *shape*
# and survive a world resize instead of being flushed wholesale — a
# resize back to a previously-seen shape (the common preemption-then-
# recovery case) reuses them. 'auto' enables this only on loopback rank
# threads: a process-path re-form tears down the XLA backend
# (clear_backends), so compiled programs cannot outlive the world there.
def elastic_warm_enabled() -> bool:
    val = (get(ELASTIC_WARM, "auto") or "auto").strip().lower()
    if val in ("1", "true", "yes", "on"):
        return True
    if val in ("0", "false", "no", "off"):
        return False
    return _lbctx.current() is not None


def donation_effective(platform: str) -> bool:
    """Whether buffer donation actually recycles memory on this backend.
    The CPU backend ignores donation while still paying per-call
    bookkeeping for it, so donation-dependent optimizations gate on
    this."""
    return platform not in ("cpu",)


def pipeline_pingpong_enabled(platform: str) -> bool:
    """Ping-pong wire-buffer recycling needs real buffer donation; the CPU
    backend ignores donation, turning each recycle output into a copy —
    'auto' therefore enables it off-CPU only."""
    val = (get(PIPELINE_PINGPONG, "auto") or "auto").strip().lower()
    if val in ("1", "true", "yes", "on"):
        return True
    if val in ("0", "false", "no", "off"):
        return False
    return donation_effective(platform)


def eager_chain_enabled(platform: str) -> bool:
    """Whether eager consumer programs may chain on still-in-flight
    collective results (``Handle.result()`` / the optimizer's gradient
    sync returning before device completion). On the XLA CPU backend the
    client runs every per-device execution on one shared thread pool, so
    consumer programs racing an in-flight multi-program collective
    (chunked wire dispatch, pipelined buckets) can occupy the pool while
    blocked on the collective's outputs — starving the rendezvous of its
    remaining participants and deadlocking the process. 'auto' therefore
    chains off-CPU only;
    on CPU results materialize before consumer math sees them."""
    val = (get(EAGER_CHAIN, "auto") or "auto").strip().lower()
    if val in ("1", "true", "yes", "on"):
        return True
    if val in ("0", "false", "no", "off"):
        return False
    return platform not in ("cpu",)
