"""Unified metrics registry: counters/gauges/histograms with labels,
Prometheus exposition, and per-rank views.

The reference Horovod's operational surfaces — the timeline's NEGOTIATE
lanes and the stall inspector naming lagging ranks — answer "where does
time go" and "which rank is slow". This module is the rebuild's one
telemetry namespace for those questions: every subsystem that used to
keep an ad-hoc stats dict (``fusion_stats``, ``dispatch_cache_stats``,
``health_stats`` retry counters) now records into — or mirrors onto —
instruments registered **here**, and two exposition surfaces read them
back:

* ``GET /metrics`` — Prometheus text format, served by the launcher KV
  server (``runner/http_kv.py``) and, per worker, by a standalone
  exposition server on ``HVD_METRICS_PORT`` (+ process rank);
* ``hvd.metrics_dump()`` — the same samples as JSON-shaped dicts.

**Catalog discipline.** Every instrument is declared below, at module
level, with a literal name — hvdlint pass 8 (``metrics-registry``)
round-trips this catalog against docs/metrics.md in both directions and
bans ad-hoc module-level telemetry counters elsewhere in the tree, the
same pattern the knob-registry pass applies to ``utils/envs.py``.

**Worlds and the ``rank`` label.** Values live in per-world *stores*:
the process-wide store, plus one per loopback :class:`RankContext` —
a rank thread's increments land in its own store, so one rank's
counters never bleed into a peer's view (``metrics_dump()`` on a rank
thread reads that rank's world). Exposition iterates every live store
and injects the store's global rank as a ``rank`` label — unless the
series already carries one (``hvd_straggler_rounds_total{rank=...}``
names the *straggler*, not the reporter, and aggregates across
reporters).

**Overhead contract**: with ``HVD_METRICS=0`` every hot-path instrument's record method is a
cached-bool no-op (the ``utils/faults.py`` fast-path idiom).
Instruments marked ``always=True`` back a legacy ``*_stats()`` API and
keep recording regardless — they replaced equally-priced dict
mutations, so disabling them would change an existing API's behavior
without saving anything.

Deliberately light on imports (envs + the stdlib + the loopback context
seam) and deliberately on **plain** ``threading.Lock``, not the
``utils/invariants.py`` constructor seam: the metrics lock is a leaf —
nothing is ever acquired under it and it never blocks on anything — so
routing it through the cooperative scheduler would only multiply
hvdsched's schedule space without adding a single explorable conflict.
"""

from __future__ import annotations

import json
import threading
import weakref

from .loopback import context as _lbctx
from .utils import envs

__all__ = [
    "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
    "enabled", "refresh", "set_enabled", "instruments", "snapshot",
    "delta", "prometheus_text", "dump", "metrics_dump", "serve",
    "maybe_serve", "stop_serving", "reset",
]

# --------------------------------------------------------------------------
# enable gate (cached; near-zero when off)
# --------------------------------------------------------------------------

_force_enabled: bool | None = None  # tests/bench override; None = knob


def _read_enabled() -> bool:
    if _force_enabled is not None:
        return _force_enabled
    return envs.get_bool(envs.METRICS, True)


_enabled = _read_enabled()


def enabled() -> bool:
    """Whether hot-path instruments record (``HVD_METRICS``, default on).
    ``always=True`` instruments (legacy ``*_stats()`` storage) record
    regardless — see the module docstring's overhead contract."""
    return _enabled


def refresh() -> None:
    """Re-read ``HVD_METRICS`` (tests toggle it after import)."""
    global _enabled
    _enabled = _read_enabled()


def set_enabled(value: bool | None) -> None:
    """Force the gate on/off (``None`` restores the knob) — the bench's
    interleaved on/off passes and tests use this; production uses the
    knob."""
    global _force_enabled
    _force_enabled = value
    refresh()


# --------------------------------------------------------------------------
# per-world value stores
# --------------------------------------------------------------------------

_mu = threading.Lock()  # leaf lock: guards stores + series maps only


class _Store:
    """One world's sample values: ``{(name, labelitems): value}`` where
    ``labelitems`` is a sorted tuple of ``(label, value)`` pairs.
    Histogram series hold a ``_Hist``."""

    __slots__ = ("values", "rank")

    def __init__(self, rank: str = ""):
        self.values: dict = {}
        self.rank = rank  # exposition's injected rank label ("" unknown)


class _Hist:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, nbuckets: int):
        self.counts = [0] * nbuckets  # cumulative at exposition, raw here
        self.sum = 0.0
        self.count = 0


_process_store = _Store()
# RankContext -> _Store; weak keys so an elastic run's dead worlds don't
# pin their stores (RankContext carries __weakref__ for exactly this).
_ctx_stores: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _store() -> _Store:
    """The calling thread's world store (rank ctx or process-wide)."""
    ctx = _lbctx.current()
    if ctx is None:
        return _process_store
    store = _ctx_stores.get(ctx)
    if store is None:
        with _mu:
            store = _ctx_stores.get(ctx)
            if store is None:
                store = _Store(rank=str(ctx.rank))
                _ctx_stores[ctx] = store
    return store


def _process_rank_label() -> str:
    """The process store's rank label: the launcher-seeded process rank
    when this is a worker, else empty (single-controller drivers have no
    rank identity worth asserting)."""
    r = envs.get(envs.RANK)
    return r if r is not None else ""


def _all_stores() -> list[_Store]:
    """Every live store, process store first (exposition iterates these;
    rank stores carry their rank label)."""
    _process_store.rank = _process_rank_label()
    with _mu:
        return [_process_store] + sorted(
            _ctx_stores.values(), key=lambda s: s.rank)


def reset_all(*instruments) -> None:
    """Drop the named instruments' series in EVERY live store — the
    process store and all rank worlds' (``_Instrument.reset`` only
    touches the calling thread's own store). Bench lanes that run
    several loopback worlds in one process use this to isolate each
    lane's counters."""
    names = {inst.name for inst in instruments}
    with _mu:
        for store in [_process_store] + list(_ctx_stores.values()):
            for k in [k for k in store.values if k[0] in names]:
                del store.values[k]


# --------------------------------------------------------------------------
# instruments
# --------------------------------------------------------------------------

_registry: "dict[str, _Instrument]" = {}


class _Instrument:
    kind = "untyped"

    def __init__(self, name: str, help: str, labels=(),
                 always: bool = False):
        self.name = name
        self.help = help
        self.labelnames = tuple(labels)
        self.always = always

    # -- recording ---------------------------------------------------------

    def _on(self) -> bool:
        return _enabled or self.always

    def _key(self, labels) -> tuple:
        if labels is None:
            if self.labelnames:
                raise ValueError(
                    f"{self.name} requires labels {self.labelnames}")
            return (self.name, ())
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name} labels {sorted(labels)} != declared "
                f"{sorted(self.labelnames)}")
        return (self.name,
                tuple(sorted((k, str(v)) for k, v in labels.items())))

    # -- reading -----------------------------------------------------------

    def series(self, store: _Store | None = None) -> dict:
        """``{labelitems: value}`` for this instrument in ``store``
        (default: the calling thread's world)."""
        store = store if store is not None else _store()
        with _mu:
            return {k[1]: v for k, v in store.values.items()
                    if k[0] == self.name}

    def value(self, labels=None, default=0.0):
        key = self._key(labels)
        store = _store()  # resolve BEFORE _mu: a first-touch store
        with _mu:         # creation re-acquires the registry lock
            return store.values.get(key, default)

    def reset(self) -> None:
        """Drop this instrument's series in the calling thread's world
        (the legacy ``reset_stats()`` surfaces)."""
        store = _store()
        with _mu:
            for k in [k for k in store.values if k[0] == self.name]:
                del store.values[k]

    def bind(self, labels=None) -> "_Bound":
        """Pre-resolve a label set into a bound series handle: the
        label-validation + sort cost is paid once, and the hot path
        (``inc``/``set``/``observe`` on the handle) is a dict update
        under the leaf lock. Callers on per-call hot paths (the fusion
        scheduler's per-tenant counters) cache these."""
        return _Bound(self, self._key(labels))


# Shared recording bodies: the unbound instrument methods and the bound
# handles both land here, so the storage semantics live in one place.

def _rec_add(inst: "_Instrument", key: tuple, amount: float) -> None:
    if not inst._on():
        return
    store = _store()
    with _mu:
        store.values[key] = store.values.get(key, 0.0) + amount


def _rec_set(inst: "_Instrument", key: tuple, value: float) -> None:
    if not inst._on():
        return
    store = _store()
    with _mu:
        store.values[key] = float(value)


def _rec_observe(inst: "Histogram", key: tuple, value: float) -> None:
    if not inst._on():
        return
    store = _store()
    with _mu:
        h = store.values.get(key)
        if h is None:
            h = store.values[key] = _Hist(len(inst.buckets))
        for i, bound in enumerate(inst.buckets):
            if value <= bound:
                h.counts[i] += 1
                break
        # past the last bound: lands only in the implicit +Inf bucket,
        # which exposition derives from the total count
        h.sum += value
        h.count += 1


class _Bound:
    __slots__ = ("inst", "key")

    def __init__(self, inst: "_Instrument", key: tuple):
        self.inst = inst
        self.key = key

    def inc(self, amount: float = 1) -> None:
        _rec_add(self.inst, self.key, amount)

    def set(self, value: float) -> None:
        _rec_set(self.inst, self.key, value)

    def observe(self, value: float) -> None:
        _rec_observe(self.inst, self.key, value)


class Counter(_Instrument):
    kind = "counter"

    def inc(self, amount: float = 1, labels=None) -> None:
        _rec_add(self, self._key(labels), amount)


class Gauge(_Instrument):
    kind = "gauge"

    def set(self, value: float, labels=None) -> None:
        _rec_set(self, self._key(labels), value)

    def add(self, amount: float, labels=None) -> None:
        _rec_add(self, self._key(labels), amount)


# Default histogram buckets: negotiation rounds over an HTTP KV span
# single-digit ms (loopback, one host) to seconds (pod-scale fan-in);
# the straggler threshold default (1 s) sits inside the range.
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0)


class Histogram(_Instrument):
    kind = "histogram"

    def __init__(self, name: str, help: str, labels=(),
                 buckets=DEFAULT_BUCKETS, always: bool = False):
        super().__init__(name, help, labels, always)
        self.buckets = tuple(sorted(buckets))

    def observe(self, value: float, labels=None) -> None:
        _rec_observe(self, self._key(labels), value)


def _register(inst: _Instrument) -> _Instrument:
    if inst.name in _registry:
        raise ValueError(f"metric {inst.name!r} already registered")
    _registry[inst.name] = inst
    return inst


def counter(name: str, help: str, labels=(), always: bool = False) -> Counter:
    return _register(Counter(name, help, labels, always))


def gauge(name: str, help: str, labels=(), always: bool = False) -> Gauge:
    return _register(Gauge(name, help, labels, always))


def histogram(name: str, help: str, labels=(), buckets=DEFAULT_BUCKETS,
              always: bool = False) -> Histogram:
    return _register(Histogram(name, help, labels, buckets, always))


def instruments() -> dict:
    """The registered catalog: ``{name: instrument}``."""
    return dict(_registry)


# --------------------------------------------------------------------------
# THE INSTRUMENT CATALOG
# (docs/metrics.md round-trips with this block — hvdlint pass 8)
# --------------------------------------------------------------------------

# -- negotiation protocol (engine_service.KVTransport / DynamicService) ----
NEGOTIATION_ROUNDS = counter(
    "hvd_negotiation_rounds_total",
    "Busy negotiation rounds (cycles with local work pending).",
    labels=("process_set",))
NEGOTIATION_ROUND_SECONDS = histogram(
    "hvd_negotiation_round_seconds",
    "Wall time of one busy negotiation exchange (publish -> all "
    "members' frames gathered).",
    labels=("process_set",))
NEGOTIATION_SUBMIT_LAG = histogram(
    "hvd_negotiation_submit_lag_seconds",
    "Per-rank submit->ready breakdown: how far behind the round's first "
    "submitter each rank's frame reached the KV server (server receipt "
    "clock, skew-free).",
    labels=("rank",))
STRAGGLER_ROUNDS = counter(
    "hvd_straggler_rounds_total",
    "Rounds in which the labeled global rank was last to submit by more "
    "than HVD_STRAGGLER_THRESHOLD (the stall-check analog).",
    labels=("rank",))
RESPONSE_CACHE_HITS = counter(
    "hvd_response_cache_hits_total",
    "Negotiation requests served locally from the coordinator "
    "ResponseCache (HVD_RESPONSE_CACHE) — zero KV rounds.",
    labels=("process_set",))
RESPONSE_CACHE_MISSES = counter(
    "hvd_response_cache_misses_total",
    "Cacheable negotiation requests that took a full round (entry "
    "absent, unconfirmed, invalidated, or a join in flight).",
    labels=("process_set",))

# -- KV transport (runner/http_kv.KVClient) --------------------------------
KV_OPS = counter(
    "hvd_kv_ops_total",
    "KV client operations by verb (gather = one server-side long-poll); "
    "divide by hvd_negotiation_rounds_total for KV ops/round.",
    labels=("op",))

# -- fusion scheduler (ops/fusion_cycle.py) --------------------------------
FUSION_FLUSHES = counter(
    "hvd_fusion_flushes_total",
    "Fusion-cycle queue flushes by trigger and tenant (process set).",
    labels=("process_set", "trigger"))
FUSION_FLUSHED_TENSORS = counter(
    "hvd_fusion_flushed_tensors_total",
    "Tensors coalesced through fusion-cycle flushes, per tenant.",
    labels=("process_set",))
FUSION_FLUSHED_BYTES = counter(
    "hvd_fusion_flushed_bytes_total",
    "Payload bytes coalesced through fusion-cycle flushes, per tenant.",
    labels=("process_set",))
FUSION_ENQUEUED_TENSORS = counter(
    "hvd_fusion_enqueued_tensors_total",
    "Async submissions accepted into fusion-cycle pending queues, per "
    "tenant.",
    labels=("process_set",))
FUSION_PENDING_BYTES = gauge(
    "hvd_fusion_pending_bytes",
    "Bytes currently queued across all fusion-cycle pending queues "
    "(backpressure drains at HVD_FUSION_MAX_PENDING).")
PIPELINE_INFLIGHT_DEPTH = gauge(
    "hvd_pipeline_inflight_depth",
    "Device-incomplete earlier flushes observed at the last executor "
    "slot admission (docs/pipeline.md overlap semantics).")

# -- multi-tenant QoS (qos.py; docs/qos.md) --------------------------------
QOS_ADMISSION_WAIT = histogram(
    "hvd_qos_admission_wait_seconds",
    "Time a flush batch spent parked in the QoS admission gate (submit "
    "-> grant), per tenant (process set).",
    labels=("process_set",))
QOS_GRANTED_BYTES = counter(
    "hvd_qos_granted_bytes_total",
    "Payload bytes granted into the flush executor's slots by the QoS "
    "arbiter, per tenant.",
    labels=("process_set",))
QOS_SLOT_SHARE = gauge(
    "hvd_qos_slot_share",
    "Tenant's cumulative share (0-1) of all bytes granted into the "
    "executor slots — converges to the configured weight ratio under "
    "saturation.",
    labels=("process_set",))
QOS_SHED = counter(
    "hvd_qos_shed_total",
    "Async submissions shed at enqueue by a tenant pending-bytes quota "
    "(policy=shed); the handle raises QosAdmissionError.",
    labels=("process_set",))
QOS_QUOTA_BLOCKS = counter(
    "hvd_qos_quota_blocks_total",
    "Producer enqueues that blocked on a tenant pending-bytes quota "
    "(policy=block) until in-flight work settled.",
    labels=("process_set",))

# -- step capture (ops/step_capture.py) ------------------------------------
STEP_CAPTURE_PHASE = gauge(
    "hvd_step_capture_phase",
    "Capture lifecycle phase: 0 idle, 1 record, 2 replay (armed), "
    "3 replayed, 4 bypass.")
STEP_CAPTURE_STEPS = counter(
    "hvd_step_capture_steps_total",
    "Step-capture lifecycle events by kind (recorded / replayed / "
    "fallback / invalidated / uncapturable).",
    labels=("event",))

# -- GSPMD cached-program fast path (ops/gspmd_cache.py) -------------------
GSPMD_CACHE_PHASE = gauge(
    "hvd_gspmd_cache_phase",
    "GSPMD cached-step lifecycle phase (the step-capture vocabulary): "
    "0 idle, 1 record (building), 3 replayed, 4 bypass.")
GSPMD_CACHE_STEPS = counter(
    "hvd_gspmd_cache_steps_total",
    "GSPMD cached-step lifecycle events by kind (recorded / replayed / "
    "fallback / invalidated / bypass).",
    labels=("event",))
GSPMD_PASSTHROUGH_SYNCS = counter(
    "hvd_gspmd_passthrough_syncs_total",
    "Gradient syncs traced through DistributedOptimizer's GSPMD "
    "passthrough branch (once per TRACE, not per step — frozen while "
    "cached steps replay).")

# -- eager DistributedOptimizer.update, stage 2 (optim/__init__.py) ---------
OPTIMIZER_INNER_UPDATES = counter(
    "hvd_optimizer_inner_updates_total",
    "Eager DistributedOptimizer inner updates by how they ran: compiled "
    "(the wrapped optimizer's update as one jitted program) / "
    "direct_extra_args (operation by operation, because extra_args held "
    "a leaf jit cannot take); and trace: times that program was traced "
    "(once per tree structure / shape / dtype; a steady job reads 1).",
    labels=("event",))

# -- TransformerLM "full" attention (models/transformer.py) -----------------
ATTENTION_CALLS = counter(
    "hvd_attention_calls_total",
    "TransformerLM full-mode Attention calls by how they were traced: "
    "blocked (Pallas kernels, scores stay in VMEM) / materialised (S x S "
    "logits and probabilities), causal; blocked_block_diffusion / "
    "materialised_block_diffusion the same two under the block-diffusion "
    "mask over a doubled sequence; and, in every mode, its q / k / v "
    "projections by how each product was stated: projection_flat (a "
    "two-dimensional product over a flat view of the [d_model, heads, "
    "head_dim] leaf: 32 heads and up, or heads of 128 and up) / "
    "projection_dense_general (the contraction over the "
    "three-dimensional leaf). Once per call per TRACE, not per step.",
    labels=("path",))
ATTENTION_SHAPE = gauge(
    "hvd_attention_last_trace",
    "The last traced full-mode Attention call: head_dim, and "
    "visible_tile_share (score tiles the blocked kernels' sweeps visit "
    "over the tiles of the rows x rows square, from the static mask; 1 "
    "on the materialised path, which computes the square).",
    labels=("what",))

# -- traced gradient sync (ops/traced_exchange.py) --------------------------
TRACED_EXCHANGE = counter(
    "hvd_traced_exchange_total",
    "Gradient leaves of a traced DistributedOptimizer / value_and_grad "
    "sync by what was emitted for them: permute_rounds (ring "
    "reduce-scatter + all-gather as rounds of collective-permutes, which "
    "the TPU compiler runs asynchronously) / psum (one "
    "lax.psum a leaf). Once per leaf per TRACE, not per step.",
    labels=("path",))
TRACED_EXCHANGE_SHAPE = gauge(
    "hvd_traced_exchange_last_trace",
    "The permute-round emission of the last traced sync: buckets (groups "
    "of leaves emitted together, in backward order) and rounds (2(k-1) a "
    "leaf on the rounds; two permutes a round where it travels both "
    "ways).",
    labels=("what",))

# -- expert layers (parallel/moe.py) -----------------------------------------
MOE_CALLS = counter(
    "hvd_moe_calls_total",
    "Expert-layer calls by how they were traced: held_share (this chip's "
    "share of the experts: dropless, rows sorted by expert, grouped matrix "
    "products, no exchange; every such call) / held_share_short_buffer "
    "(those of them that work in chunks of twice the expected load, "
    "the chunks after the first in a loop the load sizes: fewer than half "
    "of the routed experts held) / alltoall "
    "(moe_alltoall: one expert a chip, capacity buckets over two "
    "all-to-alls); and the routers by their scores: router_softmax "
    "(route_top_k) / router_sigmoid_bias (route_sigmoid_top_k). Once per "
    "call per TRACE, not per step.",
    labels=("path",))
MOE_SHAPE = gauge(
    "hvd_moe_last_trace",
    "The last traced held_share expert layer: experts_held (on this chip), "
    "experts_routed (the router's width), top_k (picks a token), "
    "buffer_rows_short (rows of a chunk of its buffer, all a step works "
    "on while the load fits; 0 where the one chunk is every pair); "
    "router_softmax (1 where the last traced router scored by a softmax "
    "over all experts, 0 by sigmoids plus a selection bias).",
    labels=("what",))

# -- dispatch plan cache (ops/dispatch_cache.py; backs
#    hvd.dispatch_cache_stats() -- always on) ------------------------------
DISPATCH_HITS = counter(
    "hvd_dispatch_plan_hits_total",
    "Dispatch-plan cache hits by source (call / flush / step / gspmd).",
    labels=("source",), always=True)
DISPATCH_MISSES = counter(
    "hvd_dispatch_plan_misses_total",
    "Dispatch-plan cache misses (plan built per call).", always=True)
DISPATCH_INVALIDATIONS = counter(
    "hvd_dispatch_plan_invalidations_total",
    "Plans dropped by epoch flushes / service resets / removals.",
    always=True)
DISPATCH_EVICTIONS = counter(
    "hvd_dispatch_plan_evictions_total",
    "Plans LRU-evicted past HVD_CACHE_CAPACITY.", always=True)
DISPATCH_NEGOTIATION_SKIPS = counter(
    "hvd_dispatch_negotiation_skips_total",
    "Negotiation rounds skipped (pinned no-service decision or engine "
    "response-cache hit).", always=True)
DISPATCH_CHUNKED_BUILDS = counter(
    "hvd_dispatch_chunked_builds_total",
    "Chunk-pipelined plan variants built (fused wire buffers past "
    "HVD_PIPELINE_THRESHOLD).", always=True)
DISPATCH_STEP_BUILDS = counter(
    "hvd_dispatch_step_builds_total",
    "Whole-step capture plans built (ops/step_capture.py).", always=True)
DISPATCH_GSPMD_BUILDS = counter(
    "hvd_dispatch_gspmd_builds_total",
    "Compiled GSPMD step programs built (ops/gspmd_cache.py: one "
    "lower+compile per new step signature).", always=True)

# -- retry ladder (utils/retry.py; backs hvd.health_stats()["retries"]
#    -- always on) ---------------------------------------------------------
RETRY_RETRIES = counter(
    "hvd_retry_retries_total",
    "Retries taken per RPC/KV site (the HVD_RETRY_* backoff ladder).",
    labels=("site",), always=True)
RETRY_GIVEUPS = counter(
    "hvd_retry_giveups_total",
    "Retryable failures that exhausted attempts/deadline per site.",
    labels=("site",), always=True)

# -- health watchdog (health.py) -------------------------------------------
HEALTH_BEATS = counter(
    "hvd_health_beats_total",
    "Liveness beats published by this rank's watchdogs.")
HEALTH_BEAT_ERRORS = counter(
    "hvd_health_beat_errors_total",
    "Beat publishes that failed through the whole retry ladder.")
HEALTH_PEER_FAILURES = counter(
    "hvd_health_peer_failures_total",
    "Peer-death decisions, labeled with the dead global rank.",
    labels=("rank",))

# -- fault injection (utils/faults.py) -------------------------------------
FAULT_FIRES = counter(
    "hvd_fault_fires_total",
    "Injected faults fired per site (HVD_FAULT_SPEC chaos runs only).",
    labels=("site",))

# -- elastic churn / warm re-form (elastic/, docs/elastic.md) --------------
ELASTIC_EVENTS = counter(
    "hvd_elastic_events_total",
    "Elastic membership + recovery events by kind: scripted churn "
    "(add / remove / preempt), worker-side recoveries (hosts-updated "
    "interrupt, peer-failure restore).",
    labels=("kind",))
ELASTIC_REFORM_SECONDS = histogram(
    "hvd_elastic_reform_seconds",
    "Worker-side re-form duration: interrupt/failure caught -> "
    "re-rendezvoused into the new round, state synced, training "
    "re-entered (the recovery-time SLO numerator).",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 45.0, 90.0, 180.0))
ELASTIC_STEPS_LOST = counter(
    "hvd_elastic_steps_lost_total",
    "In-flight steps rolled back by a failure restore (commit-per-step "
    "convention: each HorovodInternalError recovery counts its one "
    "uncommitted step; graceful interrupts count zero).")
ELASTIC_WARM_REUSE = counter(
    "hvd_elastic_warm_reuse_total",
    "Shape-keyed state reused across an elastic re-form, by kind: "
    "plan (dispatch plans grafted from the warm pool), step (whole-step "
    "capture plans), response (coordinator response-cache entries "
    "re-armed after the warm confirmation round).",
    labels=("kind",), always=True)

# -- closed-loop autoscaling (elastic/policy.py, docs/elastic.md) ----------
ELASTIC_STEP_SECONDS = histogram(
    "hvd_elastic_step_seconds",
    "Wall time between consecutive elastic state commits (the per-step "
    "latency the autoscale policy's SLO rule watches).",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             30.0))
ELASTIC_SLO_VIOLATIONS = counter(
    "hvd_elastic_slo_violations_total",
    "Committed steps whose commit-to-commit wall time exceeded the "
    "HVD_AUTOSCALE_SLO_MS target (recorded only with a nonzero target).")
ELASTIC_POLICY_DECISIONS = counter(
    "hvd_elastic_policy_decisions_total",
    "Autoscale policy decisions by action (add / remove / evict / hold) "
    "and reason (slo-breach / idle / straggler / stale-round / protected "
    "/ restore-cost / error); rank names the blamed global rank on "
    "evictions, empty otherwise.",
    labels=("action", "reason", "rank"), always=True)

# -- checkpoint state plane (checkpoint.py, docs/checkpoint.md) ------------
CKPT_SNAPSHOT_SECONDS = histogram(
    "hvd_ckpt_snapshot_seconds",
    "Background snapshot duration on the writer thread: this rank's "
    "shard pickled + written + fsync-renamed (rank 0 adds the manifest "
    "wait/write) — off the training critical path by construction.",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0))
CKPT_SHARDS_WRITTEN = counter(
    "hvd_ckpt_shards_written_total",
    "Snapshot shards durably written by this rank (one per triggered "
    "snapshot that completed its atomic rename).")
CKPT_RESTORE_SECONDS = histogram(
    "hvd_ckpt_restore_seconds",
    "Re-form state re-sync duration: manifest-agree round entered -> "
    "attributes restored (peer shard pulls, or the degraded rank-0 "
    "broadcast). The restore half of the recovery-SLO lane.",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0))
CKPT_PEER_SHARDS_PULLED = counter(
    "hvd_ckpt_peer_shards_pulled_total",
    "Shards this rank pulled from survivors during peer-restore, by "
    "transport (hub = in-world loopback rendezvous, kv = the fallback "
    "KV channel).",
    labels=("transport",))
CKPT_RESTORE_BYTES = counter(
    "hvd_ckpt_restore_bytes_total",
    "State-restore payload bytes this rank received, by source (rank0 "
    "= served by rank 0: degraded broadcasts plus shards rank 0 "
    "happened to own; peer = shards served by other survivors). The "
    "recovery lane gates peer-restore moving strictly fewer rank0 "
    "bytes than the broadcast baseline.",
    labels=("source",))
CKPT_DEGRADED_RESTORES = counter(
    "hvd_ckpt_degraded_restores_total",
    "Re-forms that fell back to the rank-0 full-tree broadcast, by "
    "reason (quorum = too few consistent survivors, structure = the "
    "joiner's state tree shape disagreed, pull-failed = shard pulls "
    "exhausted their failover retry).",
    labels=("reason",))


# -- program spans (timeline.py; docs/timeline.md "Program spans") ---------
SPAN_SECONDS = histogram(
    "hvd_span_seconds",
    "Host wall time of one call of a program span (timeline.span: the "
    "hvd:<layer>.<stage> TraceAnnotations, e.g. init, optimizer.sync, "
    "cycle.flush, plan.run, cached_step.lookup); _sum and _count per "
    "span are the in-memory totals set-up is read from, since set-up "
    "runs before any profiler session.",
    labels=("span",),
    buckets=(0.00001, 0.00005, 0.00025, 0.001, 0.005, 0.025, 0.1, 0.5,
             2.5, 10.0, 60.0))


# --------------------------------------------------------------------------
# snapshot / delta
# --------------------------------------------------------------------------

def snapshot() -> dict:
    """Flat copy of the calling thread's world: ``{(name, labelitems):
    value}``; histogram series flatten to ``(name+"_sum"/"_count", ...)``
    entries so deltas stay numeric."""
    store = _store()
    out: dict = {}
    with _mu:
        items = list(store.values.items())
    for (name, labelitems), v in items:
        if isinstance(v, _Hist):
            out[(name + "_sum", labelitems)] = v.sum
            out[(name + "_count", labelitems)] = v.count
        else:
            out[(name, labelitems)] = v
    return out


def delta(new: dict, old: dict) -> dict:
    """Per-series difference ``new - old`` (series absent from ``old``
    count from zero; gauges subtract like everything else)."""
    return {k: v - old.get(k, 0.0) for k, v in new.items()}


# --------------------------------------------------------------------------
# exposition
# --------------------------------------------------------------------------

def _escape(value: str) -> str:
    return (value.replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _inject_store_rank(labels: dict, store_rank: str) -> dict:
    """Merged-store disambiguation: a store's global rank is injected as
    ``rank`` — or as ``reporter`` when the series already names a peer
    in its ``rank`` label (``hvd_straggler_rounds_total{rank=...}``
    names the *straggler*; the reporter keeps its own series so merged
    exposition never emits two samples with identical labels)."""
    if store_rank:
        if "rank" not in labels:
            labels["rank"] = store_rank
        elif "reporter" not in labels:
            labels["reporter"] = store_rank
    return labels


def _fmt_num(v) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def _merged_series(stores) -> dict:
    """``{(name, labelitems-after-rank-injection): value}`` across
    ``stores``. Identical label sets from different stores MERGE —
    counters and histograms sum, gauges take the last writer. Two live
    ranks never collide (the injected ``rank``/``reporter`` labels
    differ); merging covers *incarnations* of the same rank — elastic
    re-forms, a previous loopback world in the same interpreter — whose
    counter totals should accumulate, exactly like a restarted process
    behind one Prometheus target."""
    merged: dict = {}
    for store in stores:
        with _mu:
            items = list(store.values.items())
        for (name, labelitems), v in items:
            key = (name, tuple(sorted(_inject_store_rank(
                dict(labelitems), store.rank).items())))
            prior = merged.get(key)
            if prior is None:
                if isinstance(v, _Hist):
                    copy = _Hist(len(v.counts))
                    copy.counts = list(v.counts)
                    copy.sum, copy.count = v.sum, v.count
                    merged[key] = copy
                else:
                    merged[key] = v
            elif isinstance(v, _Hist):
                prior.counts = [a + b
                                for a, b in zip(prior.counts, v.counts)]
                prior.sum += v.sum
                prior.count += v.count
            else:
                inst = _registry.get(name)
                if inst is not None and inst.kind == "gauge":
                    merged[key] = v  # last incarnation wins
                else:
                    merged[key] = prior + v
    return merged


def _plain_labels(labelitems) -> str:
    if not labelitems:
        return ""
    return ("{" + ",".join(f'{k}="{_escape(str(v))}"'
                           for k, v in labelitems) + "}")


def prometheus_text(all_worlds: bool = True) -> str:
    """The ``/metrics`` payload (Prometheus text format 0.0.4): every
    registered instrument emits its HELP/TYPE header even with no
    samples yet (the CI completeness gate relies on that), then one
    sample line per merged series, the store's global rank injected as
    a ``rank`` label unless the series carries its own (then as
    ``reporter`` — see :func:`_merged_series`)."""
    stores = _all_stores() if all_worlds else [_store()]
    per_name: dict[str, list[str]] = {}
    for (name, labelitems), v in _merged_series(stores).items():
        lines = per_name.setdefault(name, [])
        labels = _plain_labels(labelitems)
        inst = _registry.get(name)
        if isinstance(v, _Hist):
            cum = 0
            bounds = inst.buckets if inst is not None else ()
            base = list(labelitems)
            for i, bound in enumerate(bounds):
                cum += v.counts[i] if i < len(v.counts) else 0
                bl = _plain_labels(
                    tuple(sorted(base + [("le", f"{bound:g}")])))
                lines.append(f"{name}_bucket{bl} {cum}")
            bl = _plain_labels(tuple(sorted(base + [("le", "+Inf")])))
            lines.append(f"{name}_bucket{bl} {v.count}")
            lines.append(f"{name}_sum{labels} {_fmt_num(v.sum)}")
            lines.append(f"{name}_count{labels} {v.count}")
        else:
            lines.append(f"{name}{labels} {_fmt_num(v)}")
    out: list[str] = []
    for name, inst in sorted(_registry.items()):
        out.append(f"# HELP {name} {_escape(inst.help)}")
        out.append(f"# TYPE {name} {inst.kind}")
        out.extend(sorted(per_name.get(name, ())))
    return "\n".join(out) + "\n"


def dump(all_worlds: bool = False) -> dict:
    """``hvd.metrics_dump()``: the registered instruments with their
    series as JSON-shaped dicts. Default scope is the calling thread's
    world (a loopback rank dumps its own view); ``all_worlds=True``
    merges every live store with injected ``rank`` labels, like
    ``/metrics``."""
    stores = _all_stores() if all_worlds else [_store()]
    out: dict = {}
    for name, inst in sorted(_registry.items()):
        entry = {"type": inst.kind, "help": inst.help,
                 "labels": list(inst.labelnames), "series": []}
        if inst.kind == "histogram":
            entry["buckets"] = list(inst.buckets)
        out[name] = entry
    for (name, labelitems), v in _merged_series(stores).items():
        entry = out.get(name)
        if entry is None:
            continue
        labels = dict(labelitems)
        if isinstance(v, _Hist):
            entry["series"].append({
                "labels": labels, "count": v.count, "sum": v.sum,
                "bucket_counts": list(v.counts)})
        else:
            entry["series"].append({"labels": labels, "value": v})
    for entry in out.values():
        entry["series"].sort(key=lambda s: sorted(s["labels"].items()))
    return out


metrics_dump = dump  # the hvd.metrics_dump alias


# --------------------------------------------------------------------------
# standalone exposition server (HVD_METRICS_PORT)
# --------------------------------------------------------------------------

_server = None
_server_thread = None


def serve(port: int = 0) -> int:
    """Serve ``GET /metrics`` (all worlds) on ``port`` from a daemon
    thread; returns the bound port. Idempotent: a running server keeps
    its port. The launcher KV server serves the same payload on its own
    ``/metrics`` route; this standalone server is for workers that do
    not own the KV server."""
    global _server, _server_thread
    with _mu:
        if _server is not None:
            return _server.server_address[1]
    import http.server

    class _Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # silence stderr chatter
            pass

        def do_GET(self):
            if self.path.rstrip("/") not in ("", "/metrics"):
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            body = prometheus_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class _Server(http.server.ThreadingHTTPServer):
        daemon_threads = True
        allow_reuse_address = True

    srv = _Server(("0.0.0.0", port), _Handler)
    thread = threading.Thread(target=srv.serve_forever, daemon=True,
                              name="hvd-metrics-server")
    with _mu:
        if _server is not None:  # lost a start race
            srv.server_close()
            return _server.server_address[1]
        _server = srv
        _server_thread = thread
    thread.start()
    return srv.server_address[1]


def maybe_serve() -> int | None:
    """Start the standalone exposition server when ``HVD_METRICS_PORT``
    is seeded (by the user or ``hvdrun --metrics-port``); the bound port
    is base + the launcher process rank so co-hosted workers don't
    collide. Called from ``runtime.init()``; loopback rank threads skip
    it — their world's KV server (same process) already serves
    ``/metrics`` for every rank."""
    if not _enabled or _lbctx.current() is not None:
        return None
    base = envs.get_int(envs.METRICS_PORT, 0)
    if base <= 0:
        return None
    port = base + envs.get_int(envs.RANK, 0)
    try:
        return serve(port)
    except OSError as e:
        from .utils import logging as hvd_logging
        hvd_logging.warning("metrics exposition server failed on port "
                            "%d: %s", port, e)
        return None


def stop_serving() -> None:
    global _server, _server_thread
    with _mu:
        srv, _server = _server, None
        _server_thread = None
    if srv is not None:
        srv.shutdown()
        srv.server_close()


# --------------------------------------------------------------------------
# test / teardown helpers
# --------------------------------------------------------------------------

def reset(all_worlds: bool = False) -> None:
    """Drop every sample in the calling thread's world (or all worlds).
    Instrument registrations survive — the catalog is static."""
    if all_worlds:
        stores = _all_stores()
    else:
        stores = [_store()]
    with _mu:
        for store in stores:
            store.values.clear()
