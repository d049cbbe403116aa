"""Steady-state dispatch plan cache for eager collectives.

The Python twin of the reference's ResponseCache fast path
(``response_cache.h:107-169``; served in ``ComputeResponseList``'s HIT
branch, ``controller.cc:73-430``): the native engine already skips the
*cross-process* metadata exchange for repeated collectives, but every
eager call still paid the full *per-call Python dispatch* — exception-probed
mode detection, bundle materialization, mesh hashing through several
``lru_cache`` layers, fusion re-bucketing, negotiation/autotune/timeline
bookkeeping. A :class:`DispatchPlan` captures all of those decisions on the
first call; subsequent calls with the same key go straight from user tensor
to the compiled ``jit(shard_map(...))`` invocation.

Keys cover (op kind, user name, per-rank shape, dtype, process-set key,
reduce op, pre/post scale, hierarchical flag) — anything that changes the
compiled program or the negotiated metadata. Capacity and the off switch
ride the existing ``HVD_CACHE_CAPACITY`` knob (reference default 1024,
``global_state.h:89``; 0 disables caching entirely). The whole cache is
flushed ("invalidated") when the runtime generation changes
(``shutdown()``/``init()``), when a process set is removed, when the
negotiation services reset, or when a knob override changes (the autotuner
retunes ``FUSION_THRESHOLD``/``HIERARCHICAL_ALLREDUCE``/… — any of which
changes plan contents).

Statistics surface through :func:`stats` (exported as
``hvd.dispatch_cache_stats()``) and, when a timeline is recording, as
instant ``PLAN_HIT``/``PLAN_MISS`` events per op lane.

The cache has two clients: direct eager calls, and the cycle-driven
fusion scheduler (``ops/fusion_cycle.py``), whose single-controller
flushes coalesce a pending queue into one ``grouped_allreduce`` /
``grouped_broadcast`` — a steady-state training loop's flush signature
repeats every step, so the coalesced dispatch is a plan HIT straight into
the compiled fuse+wire programs (this pairing is what makes the cycle
flush cheap enough to sit on the async hot path).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable

import zlib as _zlib

from .. import autotune as _autotune
from .. import conformance as _conformance
from .. import metrics as _metrics
from .. import timeline as _timeline
from ..utils import envs
from ..utils import invariants as _inv

# Program spans (docs/timeline.md): the lookup, the build on a miss
# (:func:`lookup_or_build`), and a plan's run. ``plan.run`` carries each
# plan's own Chrome activity (ALLREDUCE, GROUPED_BROADCAST, ...).
_LOOKUP = _timeline.span("plan.lookup")
_BUILD = _timeline.span("plan.build")
_RUN = _timeline.span("plan.run")


class DispatchPlan:
    """One fully-resolved eager dispatch: negotiation decision, payload
    accounting, timeline labels, and the executor closure wrapping the
    compiled program. ``negotiate`` is ``None`` when the plan pinned the
    no-service decision (single-process job / non-member) — the per-call
    ``get_service`` + auto-name round is skipped entirely.

    ``variant`` distinguishes the one-wire-program composition
    (``"fused"``) from the chunk-pipelined one (``"chunked"``, fused wire
    buffers past ``HVD_PIPELINE_THRESHOLD`` split into ``pieces``
    back-to-back collective programs — see docs/pipeline.md)."""

    __slots__ = ("label", "activity", "nbytes", "negotiate", "execute",
                 "variant", "pieces")

    def __init__(self, label: str, activity: str, nbytes: int | None,
                 negotiate: Callable | None, execute: Callable,
                 variant: str = "fused", pieces: int = 1):
        self.label = label
        self.activity = activity
        self.nbytes = nbytes
        self.negotiate = negotiate
        self.execute = execute
        self.variant = variant
        self.pieces = pieces

    def run(self, arg):
        if self.negotiate is None:
            note_negotiation_skip()
        else:
            self.negotiate()
        if self.nbytes is not None:
            _autotune.record(self.nbytes)
        with _RUN(self.label, self.activity, variant=self.variant):
            return self.execute(arg)


# Cached negative decision: this signature can never be planned (e.g.
# multi-process allgather, whose program shape depends on the negotiated
# recv_splits). Stored like a plan so repeated calls skip both the rebuild
# attempt AND the miss counter.
UNPLANNABLE = object()

_lock = _inv.make_lock("dispatch_cache.lock")
_plans: "OrderedDict[tuple, DispatchPlan]" = OrderedDict()
_epoch: tuple | None = None

# --------------------------------------------------------------------------
# Elastic warm re-form (docs/elastic.md): instead of dropping every plan
# when a world resizes, the re-form teardown SHELVES the store keyed by
# process-set shape (world scope, size, own rank), and a later re-form
# back to that shape adopts it as a WARM POOL. Warm plans are never
# served from the pool directly — negotiation names must be re-derived
# through the normal build path so auto-name counters stay in lockstep
# on every member (a fresh replacement rank has no pool and builds cold)
# — instead `store()` grafts a pool plan's compiled `execute` stage onto
# the newly built plan when the keys AND derived negotiation names
# match, skipping the first-call retrace/recompile. A genuinely new
# shape simply never matches its shelf entry; registered-process-set
# keys are excluded (their numeric ids are not stable across worlds), so
# a resize invalidates exactly those affected sets.
# --------------------------------------------------------------------------

# Shapes retained process-wide (LRU). One loopback elastic run touches
# up to world_max shapes per size it visits (one per (size, rank)): a
# world-W churn cycle keeps ~3W shape keys live at once (W at the old
# size, W-1 at the new, W re-shelved before the next round drains its
# takes). The static floor covers small worlds; past it the cap scales
# with the largest world currently shelved so a world-16 cycle cannot
# evict its own shapes mid-cycle (ISSUE 15 shelf sizing).
_SHELF_SHAPES = 32
_shelf: "OrderedDict[tuple, dict]" = OrderedDict()
_warm_plans: dict = {}  # non-loopback warm pool (loopback: ctx.warm_plans)


def _shelf_cap() -> int:
    """Caller holds ``_lock``. Shape layout: (scope, size, rank) —
    index 1 is the world size."""
    worlds = [k[1] for k in _shelf
              if len(k) > 1 and isinstance(k[1], int)]
    return max(_SHELF_SHAPES, 4 * max(worlds, default=0))


def _current_shape() -> tuple | None:
    """Shape key of this thread's world: (world scope, size, rank).
    Loopback scopes by the LoopbackWorld name so one world's re-forms
    reuse each other's shelves but distinct worlds never cross."""
    from .. import runtime
    if not runtime.is_initialized():
        return None
    from ..loopback import context as _lbctx
    ctx = _lbctx.current()
    scope = ctx.world.name if ctx is not None else "proc"
    return (scope, runtime.process_count(), runtime.process_rank())


def _restorable(key: tuple, plan) -> bool:
    if plan is UNPLANNABLE:
        return False
    if getattr(plan, "variant", None) == "gspmd":
        # A compiled GSPMD step bakes the old world's device assignment
        # into the executable; a re-formed world (even at the same
        # shape) may map ranks to different devices, so these never
        # ride the warm shelf — the first warm call re-lowers.
        return False
    if getattr(plan, "variant", None) == "step":
        return bool(getattr(plan, "rebindable", False))
    # Eager plan keys carry the pset dispatch_key at index 4: "g" (an
    # unregistered global view), id 0 (THE global set — every world
    # registers it as 0), and rank tuples are self-describing across
    # worlds; other registered ids are not (a re-formed world may hand
    # the same number to a different rank list) — those stay flushed,
    # which is the "invalidate exactly the affected process sets" rule.
    if len(key) > 4 and isinstance(key[4], int) \
            and not isinstance(key[4], bool):
        return key[4] == 0
    return True


def shelve_for_reform() -> int:
    """Move this world's restorable plans onto the shape-keyed shelf
    (called by the re-form teardown BEFORE the store is invalidated).
    Unconsumed warm-pool leftovers ride along — they are plans of this
    same shape a short incarnation never got to rebuild."""
    if not envs.elastic_warm_enabled() or capacity() <= 0:
        return 0
    shape = _current_shape()
    if shape is None:
        return 0
    global _warm_plans
    epoch = envs.override_epoch()
    ctx = _ctx_store()
    plans = ctx.plans if ctx is not None else _plans
    with _lock:
        keep = {k: p for k, p in plans.items() if _restorable(k, p)}
        for k in keep:
            plans.pop(k, None)
        pool = ctx.warm_plans if ctx is not None else _warm_plans
        for k, p in (pool or {}).items():
            keep.setdefault(k, p)
        if ctx is not None:
            ctx.warm_plans = None
        else:
            _warm_plans = {}
        if not keep:
            return 0
        merged = _shelf.get(shape)
        if merged is not None and merged["epoch"] == epoch:
            merged["plans"].update(keep)
        else:
            _shelf[shape] = {"plans": keep, "epoch": epoch}
        _shelf.move_to_end(shape)
        cap = _shelf_cap()
        while len(_shelf) > cap:
            _shelf.popitem(last=False)
        _conformance.record(
            "ops/dispatch_cache.py::shelve_for_reform", "shelve",
            (shape, len(keep)))
        return len(keep)


def restore_for_reform() -> int:
    """Adopt the shelf entry matching this (re-formed) world's shape as
    the warm pool (called at the end of init). Returns the pool size;
    0 when the shape was never seen, warm re-form is off, or a knob
    override changed the wire composition the shelved programs baked."""
    if not envs.elastic_warm_enabled() or capacity() <= 0:
        return 0
    shape = _current_shape()
    if shape is None:
        return 0
    global _warm_plans
    ctx = _ctx_store()
    with _lock:
        entry = _shelf.pop(shape, None)
        if entry is None:
            return 0
        if entry["epoch"] != envs.override_epoch():
            _metrics.DISPATCH_INVALIDATIONS.inc(len(entry["plans"]))
            return 0
        if ctx is not None:
            ctx.warm_plans = entry["plans"]
        else:
            _warm_plans = entry["plans"]
        _conformance.record(
            "ops/dispatch_cache.py::restore_for_reform", "restore",
            (shape, len(entry["plans"])))
        return len(entry["plans"])


def _warm_graft_locked(ctx, key: tuple, plan) -> None:
    """Graft a warm-pool plan's compiled ``execute`` onto the newly
    built ``plan`` for the same key — valid only when the re-derived
    negotiation name matches the shelved one (then the loopback
    rendezvous keys and wire composition are identical by construction).
    Caller holds ``_lock``."""
    pool = ctx.warm_plans if ctx is not None else _warm_plans
    if not pool or plan is UNPLANNABLE:
        return
    warm = pool.pop(key, None)
    if warm is None or warm is UNPLANNABLE:
        return
    if type(warm) is not type(plan) or warm.variant != plan.variant \
            or warm.pieces != plan.pieces:
        return
    if getattr(warm.negotiate, "neg_name", None) != \
            getattr(plan.negotiate, "neg_name", None):
        return
    plan.execute = warm.execute
    _metrics.ELASTIC_WARM_REUSE.inc(labels={
        "kind": "step" if plan.variant == "step" else "plan"})
    _conformance.record(
        "ops/dispatch_cache.py::_warm_graft_locked", "graft",
        (plan.variant, _zlib.crc32(repr(key).encode()) & 0xFFFFFFFF))


def _ctx_store():
    """Loopback rank threads get their own plan map: plan keys repeat
    across ranks (same op/name/shape/pset id) but the cached ``negotiate``
    closures pin each rank's OWN service and the execute closures pin its
    exchange identity — one rank's plan must never serve another.
    Counters stay process-wide (shared metrics)."""
    from ..loopback import context as _lbctx
    ctx = _lbctx.current()
    if ctx is None:
        return None
    if ctx.plans is None:
        ctx.plans = OrderedDict()
    return ctx


# Counter storage lives in the unified metrics registry (metrics.py,
# ``always=True`` instruments — recording survives HVD_METRICS=0 because
# these back the hvd.dispatch_cache_stats() API). A loopback rank's
# lookups land in its OWN registry store, matching its per-rank plan map:
# one rank's counters never bleed into a peer's view.
#
# Where a plan hit was served from: "call" (direct eager collective),
# "flush" (a fusion-cycle flush coalescing a queue), "step" (the step
# capture-and-replay program, ops/step_capture.py), or "gspmd" (a
# replayed compiled jit/pjit step, ops/gspmd_cache.py). Per-source hit
# counters keep the overlap/coalesce ratios honest when capture is on —
# a replayed step serves ONE step-plan hit where the per-flush path
# would have served one hit per flush — and put both execution modes'
# cached-program hits on one accounting surface.
_SOURCES = ("call", "flush", "step", "gspmd")
_tls = threading.local()


class dispatch_source:
    """Context manager tagging plan lookups on this thread with their
    dispatch source (see ``_SOURCES``); the default, untagged source is
    ``"call"``."""

    __slots__ = ("_source", "_prev")

    def __init__(self, source: str):
        self._source = source
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_tls, "source", None)
        _tls.source = self._source
        return self

    def __exit__(self, *exc):
        _tls.source = self._prev
        return False


def current_source() -> str:
    return getattr(_tls, "source", None) or "call"


def capacity() -> int:
    """Live capacity from ``HVD_CACHE_CAPACITY`` (0 = caching off). Read
    per lookup so tests and the autotuner can flip it at runtime."""
    return envs.cache_capacity()


def enabled() -> bool:
    return capacity() > 0


def _current_epoch() -> tuple:
    from .. import runtime
    return (runtime.generation(), envs.override_epoch())


def _flush_locked(count_invalidation: bool) -> None:
    _flush_store_locked(_plans, count_invalidation)


def _flush_store_locked(plans, count_invalidation: bool) -> None:
    _inv.assert_holding(_lock, "dispatch_cache plan-map flush")
    if count_invalidation and plans:
        _metrics.DISPATCH_INVALIDATIONS.inc(len(plans))
    plans.clear()


def _sync_epoch_locked(ctx, plans, epoch: tuple) -> None:
    """Epoch-drift flush for the resolved store (shared by lookup and
    store): a changed runtime generation / knob-override epoch drops
    every plan before the map is read or written."""
    global _epoch
    prior = ctx.plan_epoch if ctx is not None else _epoch
    if prior != epoch:
        _flush_store_locked(plans, count_invalidation=prior is not None)
        if ctx is not None:
            ctx.plan_epoch = epoch
        else:
            _epoch = epoch


def lookup(key: tuple, source: str | None = None,
           record_stats: bool = True) -> DispatchPlan | None:
    """Plan for ``key``, or None (miss / caching disabled). Epoch drift
    (re-init, knob override change) flushes before the lookup so a stale
    plan can never serve. ``source`` (default: the thread's ambient
    :class:`dispatch_source`) tags the hit counter so per-flush and
    replayed-step hits stay distinguishable. ``record_stats=False`` is
    for bookkeeping probes (the capture controller's seal/arm checks):
    the lookup itself stays silent and the hit is counted only when a
    replay actually serves (:func:`note_step_hit`), so the counters
    reflect work served, not state-machine traffic."""
    if capacity() <= 0:
        return None
    with _LOOKUP():
        epoch = _current_epoch()
        src = source or current_source()
        ctx = _ctx_store()
        plans = ctx.plans if ctx is not None else _plans
        with _lock:
            _sync_epoch_locked(ctx, plans, epoch)
            plan = plans.get(key)
            if plan is None:
                if record_stats:
                    _metrics.DISPATCH_MISSES.inc()
                return None
            plans.move_to_end(key)
            if plan is UNPLANNABLE:
                return plan  # negative decision: neither a hit nor a miss
            if record_stats:
                _metrics.DISPATCH_HITS.inc(labels={"source": src})
        if record_stats:
            _timeline.record_dispatch(plan.label, hit=True)
        return plan


def lookup_or_build(key: tuple, build: Callable):
    """The eager collectives' lookup: the plan for ``key``, built with
    ``build()`` and stored on a miss (``build`` may return
    :data:`UNPLANNABLE`; with caching disabled every call builds and
    nothing is stored)."""
    plan = lookup(key)
    if plan is None:
        with _BUILD():
            plan = build()
        store(key, plan)
    return plan


def note_step_hit() -> None:
    """Count one SERVED step-plan replay (``hits_by_source["step"]``):
    called by the capture controller when the whole-step program
    actually executes, so step hits equal replayed steps exactly — an
    armed-then-diverged step never counts."""
    _metrics.DISPATCH_HITS.inc(labels={"source": "step"})
    _timeline.record_dispatch("step", hit=True)


def note_gspmd_hit() -> None:
    """Count one SERVED compiled GSPMD step replay
    (``hits_by_source["gspmd"]``) — the gspmd twin of
    :func:`note_step_hit`: counted after the executable accepts its
    inputs, so a signature hit whose executable rejects (the divergence
    fallback) never counts."""
    _metrics.DISPATCH_HITS.inc(labels={"source": "gspmd"})
    _timeline.record_dispatch("gspmd", hit=True)


def fold_knobs(variant: str, key: tuple, *raw_knob_values) -> tuple:
    """THE store-key canonicalizer shared by the whole-step program
    caches (``step_capture._store_key`` / ``gspmd_cache``): prefix a
    content ``key`` with its plan ``variant`` and the RAW values of
    every knob the compiled program bakes in. Override-driven knob
    changes already invalidate via the cache epoch, but a raw
    ``os.environ`` change does not bump the epoch — folding the values
    into the key means a stale program can never replay.

    Axis-layout discipline: any plan whose compiled program bakes in a
    mesh-axis split carries the layout in its key — the eager
    allreduce/grouped-allreduce/allgather keys fold
    ``hierarchical.layout_key_for(pset)`` (the composed-mesh layout
    signature, ``parallel/mesh.py``), step capture folds the raw
    ``HVD_MESH_AXES`` carve, and the GSPMD cache fingerprints the full
    mesh (axis names + shape + device ids) through its shardings."""
    return (variant,) + tuple(raw_knob_values) + (key,)


def drop(key: tuple) -> bool:
    """Remove ONE plan from this thread's store (the gspmd divergence
    contract: an executable that rejected its inputs despite a
    signature hit must not serve again). Returns whether a plan was
    present. Unlike :func:`invalidate`, every other plan survives."""
    ctx = _ctx_store()
    plans = ctx.plans if ctx is not None else _plans
    with _lock:
        found = plans.pop(key, None)
        if found is not None:
            _metrics.DISPATCH_INVALIDATIONS.inc()
    return found is not None


def store(key: tuple, plan: DispatchPlan) -> None:
    """Insert ``plan`` (LRU-evicting past capacity). No-op when caching is
    disabled, so the build-per-call path stays allocation-clean."""
    global _epoch
    cap = capacity()
    if cap <= 0:
        return
    epoch = _current_epoch()
    ctx = _ctx_store()
    plans = ctx.plans if ctx is not None else _plans
    with _lock:
        if plan is not UNPLANNABLE and plan.variant == "chunked":
            _metrics.DISPATCH_CHUNKED_BUILDS.inc()
        if plan is not UNPLANNABLE and plan.variant == "step":
            _metrics.DISPATCH_STEP_BUILDS.inc()
        if plan is not UNPLANNABLE and plan.variant == "gspmd":
            _metrics.DISPATCH_GSPMD_BUILDS.inc()
        _sync_epoch_locked(ctx, plans, epoch)
        # Elastic warm re-form: adopt the shelved incarnation's compiled
        # execute stage before the first call pays the retrace/recompile.
        _warm_graft_locked(ctx, key, plan)
        plans[key] = plan
        plans.move_to_end(key)
        while len(plans) > cap:
            plans.popitem(last=False)
            _metrics.DISPATCH_EVICTIONS.inc()
    # Local event (docs/conformance.md): plan-key builds are
    # legitimately rank-asymmetric after a warm re-form (a survivor
    # hits where a fresh rank builds), so they are recorded per rank
    # but never chained cross-rank.
    _conformance.record(
        "ops/dispatch_cache.py::store", "plan_store",
        (getattr(plan, "variant", "unplannable"),
         _zlib.crc32(repr(key).encode()) & 0xFFFFFFFF))
    if plan is not UNPLANNABLE:
        _timeline.record_dispatch(plan.label, hit=False)


def invalidate(reason: str | None = None) -> int:
    """Flush every cached plan (process-set removal, service reset,
    shutdown) in this thread's world — a loopback rank invalidates its
    own store. Returns the number of plans dropped."""
    global _warm_plans
    del reason
    ctx = _ctx_store()
    plans = ctx.plans if ctx is not None else _plans
    with _lock:
        n = len(plans)
        _flush_store_locked(plans, count_invalidation=True)
        # the warm pool holds plans of THIS world's shape; whatever
        # invalidated the store (pset removal, service reset) applies
        if ctx is not None:
            ctx.warm_plans = None
        else:
            _warm_plans = {}
    return n


def note_negotiation_skip() -> None:
    """Account one negotiation round skipped — either the plan pinned the
    no-service decision, or the engine served the round from its response
    cache (``from_cache``, the reference's bitvector HIT path)."""
    _metrics.DISPATCH_NEGOTIATION_SKIPS.inc()


def stats() -> dict:
    """Plan-cache counters (the ``hvd.dispatch_cache_stats()`` API) —
    a view over the unified metrics registry, shape-identical to the
    pre-registry dicts. On a loopback rank thread the view (like the
    rank's plan map) is that rank's own."""
    by_source = {s: 0 for s in _SOURCES}
    for labelitems, v in _metrics.DISPATCH_HITS.series().items():
        by_source[dict(labelitems).get("source", "call")] = int(v)
    warm_reuses = 0
    for labelitems, v in _metrics.ELASTIC_WARM_REUSE.series().items():
        if dict(labelitems).get("kind") in ("plan", "step"):
            warm_reuses += int(v)
    ctx = _ctx_store()
    plans = ctx.plans if ctx is not None else _plans
    with _lock:
        size = len(plans)
        pool = ctx.warm_plans if ctx is not None else _warm_plans
        warm_pool = len(pool or {})
    return {
        "enabled": enabled(),
        "capacity": capacity(),
        "size": size,
        "hits": sum(by_source.values()),
        "hits_by_source": by_source,
        "misses": int(_metrics.DISPATCH_MISSES.value()),
        "invalidations": int(_metrics.DISPATCH_INVALIDATIONS.value()),
        "evictions": int(_metrics.DISPATCH_EVICTIONS.value()),
        "negotiation_skips": int(
            _metrics.DISPATCH_NEGOTIATION_SKIPS.value()),
        "chunked_builds": int(_metrics.DISPATCH_CHUNKED_BUILDS.value()),
        "step_builds": int(_metrics.DISPATCH_STEP_BUILDS.value()),
        "gspmd_builds": int(_metrics.DISPATCH_GSPMD_BUILDS.value()),
        # elastic warm re-form (docs/elastic.md): plans waiting in this
        # world's warm pool, and compiled stages grafted from it
        "warm_pool": warm_pool,
        "warm_reuses": warm_reuses,
    }


def reset_stats() -> None:
    for inst in (_metrics.DISPATCH_HITS, _metrics.DISPATCH_MISSES,
                 _metrics.DISPATCH_INVALIDATIONS,
                 _metrics.DISPATCH_EVICTIONS,
                 _metrics.DISPATCH_NEGOTIATION_SKIPS,
                 _metrics.DISPATCH_CHUNKED_BUILDS,
                 _metrics.DISPATCH_STEP_BUILDS,
                 _metrics.DISPATCH_GSPMD_BUILDS):
        inst.reset()


def reset() -> None:
    """Tests / teardown: drop plans, shelves, pools AND counters."""
    global _epoch, _warm_plans
    with _lock:
        _plans.clear()
        _epoch = None
        _shelf.clear()
        _warm_plans = {}
    reset_stats()
