"""Step capture-and-replay: compile the whole step's collective stream
into one cached program.

Even after the dispatch plan cache, the fusion cycle and the pipelined
executor cut the per-call and per-flush costs, every flush of an eager
step pays its own drain, plan lookup and launches (not measured on the
chip: docs/step_capture.md). The PR-2/3 determinism contract makes that
remaining overhead *removable*: flush composition is a pure function of
submission order plus enqueue-time negotiation names, so the per-step
collective stream is rank-deterministic and therefore **recordable**.

This module records the flush stream of one *marked* step — signatures,
bucket layouts, wire dtypes, negotiation names — as it flows through
``ops/fusion_cycle.py``, then lowers the entire step's collective work
(per-dtype fuse, grouped collectives, split, wire-buffer donation) into
ONE jitted program pair built by :func:`_plan_step_programs`, cached in
``ops/dispatch_cache.py`` under a step-signature key, and replayed on
subsequent steps with zero per-flush Python/dispatch overhead. The
Horovod API stays eager on the surface: handles, ``synchronize()``,
``result()`` behave identically; only the dispatch under them changes.

Lifecycle (``HVD_STEP_CAPTURE=1``; see docs/step_capture.md):

* ``hvd.step_marker()`` marks a step boundary (bare call per loop
  iteration, or ``with hvd.step_marker():`` around the step body). The
  bucketed ``optim.DistributedOptimizer`` gradient sync opens a region
  automatically when the knob is on and no user region is active.
* The first marked step RECORDS: every flush that drains during the
  region appends a :class:`_FlushRecord` (queue key, per-entry
  signatures, grouping, trigger) while executing eagerly as usual.
* The boundary SEALS the recording into a :class:`StepPlan` keyed by
  the stream's content signature (never by auto-generated negotiation
  names, so two schedulers fed the same stream produce byte-identical
  keys) and arms REPLAY.
* During replay, submissions are matched against the recorded stream
  and *held*; when the last recorded submission arrives, the whole
  step's collective work issues as one ``fuse``/``wire`` program pair
  (both under ``program_issue.issue_serialized``; the wire stage takes
  the fused buffers donated, exactly like the per-flush plans).
* Any divergence — shape/dtype drift, a new tensor, a different
  composition, a blocking ``synchronize`` before the stream completed,
  a barrier drain, an elastic re-form or ``abort()`` mid-step, a knob
  override epoch (the dispatch-cache epoch flush drops the plan) —
  INVALIDATES the capture: held entries execute eagerly with their
  recorded composition (correct results, no hang, no stale-plan reuse)
  and the next marked step re-records.

Multi-process (negotiation-service) streams replay with their
submission-time per-entry program composition (the joined-rank contract
forbids re-fusing them) but batch every flush's negotiation of the step
into ONE ``DynamicService.negotiate_step`` round — one KV cycle per
step instead of one per flush.

Statistics surface as the ``capture`` block of ``hvd.fusion_stats()``;
``hvd.dispatch_cache_stats()["hits_by_source"]`` separates step-plan
hits from per-flush and per-call hits so coalesce/overlap ratios stay
honest when capture is on.
"""

from __future__ import annotations

import zlib as _zlib

import jax
import jax.numpy as jnp
import numpy as np

from .. import autotune as _autotune
from .. import conformance as _conformance
from .. import metrics as _metrics
from .. import timeline as _timeline
from ..utils import envs
from ..utils import invariants as _inv
from ..utils import logging as hvd_logging
from . import dispatch_cache as _dispatch
from .program_issue import issue_serialized as _issue_serialized

# Flush triggers that mean the caller will BLOCK on the entry: a held
# entry observed through one of these before the recorded stream
# completed is a divergence (the recording predicted more submissions
# first) and must fall back eagerly so the caller can never hang.
_BLOCKING_TRIGGERS = ("synchronize", "poll")

# program span (docs/timeline.md): one replayed whole-step program
_REPLAY = _timeline.span("step_capture.replay", "STEP_REPLAY", lane="step")


def _wire_dt(src_dt, compression):
    """Wire dtype from a *signature* dtype (the tensor itself is gone by
    plan-build time) — the metadata twin of ``collectives._wire_dtype_of``:
    floating tensors travel in the compressor's wire dtype, everything
    else in its own."""
    wire = getattr(compression, "wire_dtype", None)
    if wire is not None and jnp.issubdtype(jnp.dtype(src_dt), jnp.floating):
        return jnp.dtype(wire)
    return jnp.dtype(src_dt)


class _EntryTemplate:
    """The replay-matchable shape of one recorded submission: queue key,
    grouping, tensor count, and the normalized per-tensor plan signatures
    (``collectives._plan_sig`` tuples). Negotiation names are
    deliberately NOT part of the template *signature* — auto names
    advance global counters, so keying on them would make the capture
    key depend on unrelated traffic instead of the stream's content —
    but they are retained for the seal-time duplicate check (a
    user-specified name repeated within one step needs the eager path's
    name-reuse serialization, which replay's single batched negotiation
    round cannot provide)."""

    __slots__ = ("key", "grouped", "count", "sigs", "names")

    def __init__(self, key, grouped, count, sigs, names=()):
        self.key = key
        self.grouped = grouped
        self.count = count
        self.sigs = sigs
        self.names = names

    def matches(self, entry) -> bool:
        return (entry.grouped == self.grouped
                and entry.count == self.count
                and getattr(entry, "sigs", None) == self.sigs)

    def signature(self) -> tuple:
        return (self.grouped, self.count, self.sigs)


class _FlushRecord:
    """One recorded flush: the queue spec it drained with, its entry
    templates in submission order, and the trigger that drained it."""

    __slots__ = ("spec", "templates", "trigger")

    def __init__(self, spec, templates, trigger):
        self.spec = spec
        self.templates = templates
        self.trigger = trigger

    def signature(self) -> tuple:
        return (self.templates[0].key if self.templates else (),
                tuple(t.signature() for t in self.templates))


class StepPlan(_dispatch.DispatchPlan):
    """A sealed capture: the recorded stream plus the whole-step
    executor. ``execute`` takes the held entries grouped per record (in
    template order) and returns per-record flat result lists. Stored in
    the dispatch plan cache under ``("step",) + key`` so every existing
    invalidation path (knob-override epoch, process-set removal, service
    reset, shutdown, LRU pressure) drops it like any other plan."""

    __slots__ = ("key", "records", "entries_total", "rebindable")

    def __init__(self, key, records, run_step, nbytes, pieces,
                 rebindable: bool = False):
        super().__init__("step", "STEP_REPLAY", nbytes, None, run_step,
                         variant="step", pieces=pieces)
        self.key = key
        self.records = records
        self.entries_total = sum(len(r.templates) for r in records)
        # Whether the executor survives an elastic re-form to the same
        # process-set shape (docs/elastic.md): negotiated streams over
        # the GLOBAL set resolve their service and mesh lazily per
        # replay, so their whole-step executor can be warm-grafted
        # across worlds. Single-controller streams bake mesh-bound jits,
        # and registered non-global sets bake old-world membership (the
        # numeric id may alias a different rank list after the resize) —
        # both stay world-local.
        self.rebindable = rebindable


# ---------------------------------------------------------------------------
# whole-step program construction (single-controller streams)
# ---------------------------------------------------------------------------

def _group_part(spec, sigs):
    """Per-group compile ingredients for the whole-step program: the
    fuse/wire closures (traced inside the step jits), the input
    canonicalizer, and the donation mask — the step-scope mirror of
    ``collectives._build_grouped_allreduce_plan``'s bookkeeping. A
    *group* is every recorded flush sharing one queue key (same
    op/process-set/scales/compression/root), so the whole step's
    same-signature flushes re-fuse into ONE per-dtype wire buffer set —
    the reduction is elementwise per tensor, so cross-flush fusion only
    changes wire packaging, never numerics (the PR-2 coalescing
    argument applied at step scope)."""
    from . import collectives as _coll
    from . import hierarchical
    from .reduce_ops import ReduceOp, handle_average

    count = len(sigs)
    n = spec.pset.size()
    bundled = any(s[0] == "b" for s in sigs)
    shapes = [tuple(s[1][1:]) if s[0] == "b" else tuple(s[1]) for s in sigs]
    src_dts = [jnp.dtype(s[2]) for s in sigs]
    if spec.kind == "allreduce":
        wire_dts = [_wire_dt(dt, spec.compression) for dt in src_dts]
    else:
        wire_dts = list(src_dts)
    metas = _coll._fusion_metas(shapes, src_dts, wire_dts)
    layout = None
    if spec.kind == "allreduce":
        lowered_op, post = handle_average(spec.op, n, spec.post)
        pre, post = float(spec.pre), float(post)
        hier = (lowered_op == ReduceOp.SUM
                and hierarchical.hierarchical_enabled_for(spec.pset))
        if hier:
            smap = hierarchical._hier_grouped_allreduce_smap(
                hierarchical.hierarchical_mesh(), lowered_op, pre, post,
                len(metas), bundled)
        else:
            smap = _coll._grouped_allreduce_smap(
                spec.pset.mesh(), spec.axis, lowered_op, pre, post,
                len(metas), bundled)
            # The recorded chunking decision carries into the captured
            # program: wire buckets past HVD_PIPELINE_THRESHOLD reduce
            # as HVD_PIPELINE_CHUNKS piece collectives INSIDE the step
            # program — a monolithic multi-MiB reduction measured far
            # slower than its chunked pieces (the PR-3 finding), and
            # step fusion across flushes makes buckets BIGGER, not
            # smaller.
            layout = _coll._chunk_layout(metas)
        row0 = bundled
        if layout is not None:
            piece_smap = _coll._grouped_allreduce_smap(
                spec.pset.mesh(), spec.axis, lowered_op, pre, post, 1,
                bundled)
    else:  # broadcast
        root_pos = spec.pset.ranks.index(spec.root_rank)
        smap = _coll._grouped_broadcast_smap(
            spec.pset.mesh(), spec.axis, root_pos, len(metas), bundled)
        row0 = False
    # the fuse body, donate mask, and canonicalizer are THE shared
    # builders the per-flush plans compile from — numerics and donation
    # safety cannot drift between the eager and replay paths
    donate = _coll._sig_donate_mask(metas, sigs, bundled)
    fuse = _coll._fuse_closure(metas, n, bundled)
    canon = _coll._canon_closure(shapes, n, bundled)

    if layout is None:
        def wire(fused):
            outs = list(smap(*fused))
            if row0:
                outs = [o[0] for o in outs]
            return _coll._split_fused(outs, metas, count)
    else:
        def wire(fused):
            pieces: list = [[] for _ in metas]
            for bi, a, b in layout:
                part = fused[bi][:, a:b] if bundled else fused[bi][a:b]
                out, = piece_smap(part)
                pieces[bi].append(out)
            outs = [ps[0] if len(ps) == 1
                    else jnp.concatenate(ps, axis=1 if bundled else 0)
                    for ps in pieces]
            if row0:
                outs = [o[0] for o in outs]
            return _coll._split_fused(outs, metas, count)

    nbytes = sum(int(np.prod(shp) or 1) * dt.itemsize
                 for shp, dt in zip(shapes, wire_dts))
    return {"fuse": fuse, "wire": wire, "canon": canon, "donate": donate,
            "count": count, "n_inputs": count, "n_bufs": len(metas),
            "nbytes": nbytes}


def _plan_step_programs(parts):
    """The captured step's two compiled stages — the step-scope twin of
    ``collectives._plan_fused_programs``. Stage 1 (``fuse``) packs EVERY
    record's user tensors into their per-dtype wire buffers in one
    program. Stage 2 (``wire``) runs every record's shard-mapped
    collective AND its wire-buffer split in one program, with the fused
    buffers donated — they are stage-1 outputs, so donation can only
    recycle dispatcher-owned memory (the per-record donate masks exclude
    buffers a backend's input-output forwarding could alias to a user
    array, exactly like the per-flush plans)."""
    in_slices, buf_slices = [], []
    donate: list = []
    ip = bp = 0
    for p in parts:
        in_slices.append((ip, ip + p["n_inputs"]))
        ip += p["n_inputs"]
        buf_slices.append((bp, bp + p["n_bufs"]))
        bp += p["n_bufs"]
        donate.extend(p["donate"])

    def fuse(*flat_inputs):
        bufs = []
        for p, (lo, hi) in zip(parts, in_slices):
            bufs.extend(p["fuse"](list(flat_inputs[lo:hi])))
        return tuple(bufs)

    def wire(*flat_fused):
        outs = []
        for p, (lo, hi) in zip(parts, buf_slices):
            outs.extend(p["wire"](list(flat_fused[lo:hi])))
        return tuple(outs)

    fuse_fn = _issue_serialized(jax.jit(fuse))
    wire_fn = _issue_serialized(jax.jit(
        wire, donate_argnums=tuple(i for i, d in enumerate(donate) if d)))
    return fuse_fn, wire_fn


def _fuse_groups(records):
    """Group the recorded flushes by queue key (stream order preserved
    within each group). Each group fuses into one per-dtype wire buffer
    set — a steady-state step's N bucket flushes become ONE collective
    set instead of N."""
    groups: dict = {}
    order: list = []
    for ri, rec in enumerate(records):
        key = rec.templates[0].key if rec.templates else ()
        g = groups.get(key)
        if g is None:
            g = {"spec": rec.spec, "sigs": [], "records": []}
            groups[key] = g
            order.append(g)
        sigs = [s for t in rec.templates for s in t.sigs]
        lo = len(g["sigs"])
        g["sigs"].extend(sigs)
        g["records"].append((ri, lo, lo + len(sigs)))
    return order


def _make_jit_execute(records):
    """Whole-step executor for single-controller streams: one
    fuse+wire program pair covering every recorded flush, with
    same-signature flushes re-fused across the step."""
    groups = _fuse_groups(records)
    parts = [_group_part(g["spec"], g["sigs"]) for g in groups]
    fuse_fn, wire_fn = _plan_step_programs(parts)

    def execute(entries_per_record):
        flat = []
        for g, p in zip(groups, parts):
            ts = []
            for ri, _lo, _hi in g["records"]:
                ts.extend(t for e in entries_per_record[ri]
                          for t in e.tensors)
            flat.extend(p["canon"](ts))
        outs = list(wire_fn(*fuse_fn(*flat)))
        result: list = [None] * len(records)
        pos = 0
        for g, p in zip(groups, parts):
            group_outs = outs[pos:pos + p["count"]]
            pos += p["count"]
            for ri, lo, hi in g["records"]:
                result[ri] = group_outs[lo:hi]
        return result

    return execute, sum(p["nbytes"] for p in parts)


def _make_svc_execute(records):
    """Whole-step executor for negotiated (multi-process) streams: ONE
    batched negotiation round for every flush of the step, then each
    entry's submission-time program composition — identical to what a
    joined rank reconstructs from response metadata, so active and
    joined processes keep lowering the same programs."""
    pset = records[0].spec.pset
    build_svc = records[0].spec.svc

    def execute(entries_per_record):
        from .. import engine_service
        from . import collectives as _coll
        reqs = [r for entries in entries_per_record
                for e in entries for r in e.requests]
        if reqs:
            # Resolve the service per replay, not at build: an elastic
            # re-form back to this shape rebuilds services, and lazy
            # resolution is what lets a warm-restored step plan
            # (docs/elastic.md) negotiate against the NEW world.
            svc = engine_service.get_service(pset) or build_svc
            svc.negotiate_step(reqs)
        out = []
        for rec, entries in zip(records, entries_per_record):
            spec = rec.spec
            if spec.kind == "broadcast":
                tensors = [t for e in entries for t in e.tensors]
                out.append(_coll._run_queued_broadcast(
                    tensors, spec.pset, spec.axis, spec.root_rank,
                    entries[0].label))
            else:
                outs: list = []
                for e in entries:
                    outs.extend(_coll._run_queued_allreduce(
                        e.tensors, spec.pset, spec.axis, spec.op,
                        spec.pre, spec.post, spec.compression, e.label))
                out.append(outs)
        return out

    return execute, None


# ---------------------------------------------------------------------------
# the per-scheduler capture controller
# ---------------------------------------------------------------------------

def _store_key(key: tuple) -> tuple:
    """Dispatch-cache key for a sealed capture: the stream's content
    signature PLUS the raw knob values the compiled programs bake in
    (fusion threshold -> bucket metas; pipeline threshold/chunks ->
    in-program chunk layout), canonicalized through the shared
    :func:`~.dispatch_cache.fold_knobs` discipline the GSPMD program
    cache (``ops/gspmd_cache.py``) also uses. Override-driven knob
    changes already invalidate via the cache epoch, but a raw
    os.environ change does not bump the epoch — folding the values into
    the key means a stale layout can never replay (the eager plan keys
    do the same). The composed-mesh axis carve (``HVD_MESH_AXES``) is
    folded too: a captured composed step's ICI+DCN collective stream is
    layout-specific, and a carve change must re-record rather than
    replay the old axis split."""
    from . import collectives as _coll
    return _dispatch.fold_knobs("step", key, envs.fusion_threshold_bytes(),
                                _coll._pipeline_key(), envs.mesh_axes())


# Registry mirror of the capture lifecycle (docs/metrics.md): a numeric
# phase gauge plus per-event counters, with ONE phase vocabulary shared
# across the cached-program layers — ``ops/gspmd_cache.py`` mirrors its
# lifecycle through `_lifecycle_note` onto its own instruments with
# these same codes. The per-instance `_stats` dict stays the
# `fusion_stats()["capture"]` storage (tests build standalone
# schedulers whose capture counters must not mix); the registry mirror
# is the scrapeable view.
_PHASE_CODES = {"idle": 0, "record": 1, "replay": 2, "replayed": 3,
                "bypass": 4}


def _lifecycle_note(steps_counter, phase_gauge,
                    event: str | None = None,
                    state: str | None = None) -> None:
    """Shared lifecycle mirror of the cached-program architecture: one
    event counter bump and/or one phase-gauge transition (capture and
    gspmd plans use the same event names and phase codes, so the two
    execution modes read identically on the metrics surface)."""
    if event is not None:
        steps_counter.inc(labels={"event": event})
    if state is not None:
        phase_gauge.set(_PHASE_CODES.get(state, 0))


def _note_capture(event: str | None = None,
                  state: str | None = None) -> None:
    _lifecycle_note(_metrics.STEP_CAPTURE_STEPS,
                    _metrics.STEP_CAPTURE_PHASE, event, state)


class CaptureState:
    """Capture lifecycle controller owned by one
    :class:`~horovod_tpu.ops.fusion_cycle.FusionScheduler`.

    States: ``idle`` (no region open), ``record`` (first marked step:
    flushes execute eagerly and are recorded), ``replay`` (armed with a
    sealed plan: submissions are matched and held), ``replayed`` (the
    stream completed and the captured program executed), ``bypass`` (a
    divergence or abort dropped this step back to eager until the next
    boundary). Lock order: ``_mu`` may be held while taking the dispatch
    cache lock, never while taking the scheduler's ``_mu``/``_exec_cv``
    (fallback execution and replay dispatch run outside the lock)."""

    def __init__(self, sched):
        self._sched = sched
        self._mu = _inv.make_lock("step_capture.mu")
        # tests/models override; None = follow HVD_STEP_CAPTURE
        self.force_enabled = None
        self._state = "idle"
        self._region_open = False
        self._recording = False  # unlocked fast-path flag for note_flush
        self._replaying = False  # unlocked fast-path flag for offer
        self._records: list = []
        self._plan: StepPlan | None = None
        self._last_key: tuple | None = None
        self._expect: dict = {}
        self._held: dict = {}
        self._matched = 0
        self._total = 0
        self._stats = {
            "recorded_steps": 0, "captured_flushes": 0, "plan_builds": 0,
            "replayed_steps": 0, "replayed_entries": 0, "fallbacks": 0,
            "invalidations": 0, "uncapturable_steps": 0,
        }
        # instance attribute so tests/models can stub the constructor
        self._build_plan = self._default_build_plan

    # -- configuration -----------------------------------------------------

    def enabled(self) -> bool:
        if self.force_enabled is not None:
            return bool(self.force_enabled)
        return envs.step_capture_enabled()

    def region_open(self) -> bool:
        return self._region_open

    # -- step boundaries ---------------------------------------------------

    def boundary(self, closing: bool = False) -> None:
        """Close the current step region (seal a recording / verify a
        replay) and, unless ``closing``, open the next one — armed for
        replay when a plan for the last stream is still cached."""
        if not self.enabled() and self._state == "idle" \
                and not self._region_open:
            return
        prev_state = self._state
        fallback = None
        with self._mu:
            if self._state == "record":
                self._seal_locked()
            elif self._state == "replay":
                if self._matched == 0 and not self._held:
                    # EMPTY region: nothing was submitted at all (e.g.
                    # an eval iteration between marked train steps).
                    # Nothing diverged — keep the plan and _last_key so
                    # the next non-empty step re-arms instead of
                    # re-recording forever in a train/eval alternation.
                    self._expect = {}
                    self._total = 0
                else:
                    # the step ended before the recorded stream
                    # completed: divergence by omission — no
                    # stale-plan reuse
                    fallback = self._take_held_locked()
                    self._diverge_locked()
            self._state = "idle"
            self._replaying = self._recording = False
            self._region_open = False
        if fallback:
            self._run_fallback(fallback)
        if closing or not self.enabled():
            # Lockstep decision point (docs/conformance.md): every rank
            # must close the region from the same phase.
            _conformance.record(
                "ops/step_capture.py::CaptureState.boundary", "phase",
                (prev_state, "idle"))
            return
        with self._mu:
            self._region_open = True
            plan = None
            if self._last_key is not None:
                plan = _dispatch.lookup(_store_key(self._last_key),
                                        record_stats=False)
                if not isinstance(plan, StepPlan):
                    # epoch flush / eviction / capacity 0 dropped it
                    self._stats["invalidations"] += 1
                    _note_capture("invalidated")
                    self._last_key = None
                    plan = None
            if plan is not None:
                self._arm_locked(plan)
            elif _dispatch.enabled():
                self._records = []
                self._state = "record"
                self._recording = True
            else:
                # plan cache disabled (HVD_CACHE_CAPACITY=0): a sealed
                # plan could never be stored, so recording every step
                # would only burn bookkeeping — stay eager for the region
                self._state = "bypass"
        _note_capture(state=self._state)
        # Lockstep decision point (docs/conformance.md): the boundary's
        # phase move — seal/arm/record/bypass — is rank-deterministic.
        _conformance.record(
            "ops/step_capture.py::CaptureState.boundary", "phase",
            (prev_state, self._state))
        _timeline.record_capture(
            "REPLAY" if self._replaying
            else ("RECORD" if self._recording else "BYPASS"))

    def _seal_locked(self) -> None:
        records, self._records = self._records, []
        self._recording = False
        if not records:
            return
        self._stats["recorded_steps"] += 1
        self._stats["captured_flushes"] += len(records)
        _note_capture("recorded")
        key = tuple(r.signature() for r in records)
        # Lockstep decision point (docs/conformance.md): the sealed
        # stream key every rank must derive byte-identically (hashed —
        # full signatures are long; the ring keeps the quotable form).
        _conformance.record(
            "ops/step_capture.py::CaptureState._seal_locked", "seal",
            (len(records), _zlib.crc32(repr(key).encode()) & 0xFFFFFFFF))
        cached = _dispatch.lookup(_store_key(key), record_stats=False)
        if isinstance(cached, StepPlan):
            self._last_key = key  # alternating streams reuse their plan
            return
        try:
            plan = self._build_plan(key, records)
        except Exception as exc:
            hvd_logging.error("step capture plan build failed: %s", exc)
            plan = None
        if plan is None:
            self._stats["uncapturable_steps"] += 1
            _note_capture("uncapturable")
            self._last_key = None
            return
        self._stats["plan_builds"] += 1
        _dispatch.store(_store_key(key), plan)
        self._last_key = key
        _timeline.record_capture("SEAL")

    def _default_build_plan(self, key, records):
        """StepPlan for a sealed recording, or None when the stream is
        not capturable (non-fusable kinds, unplanned entries, mixed
        single-controller/negotiated flushes)."""
        svc = records[0].spec.svc
        for rec in records:
            if rec.spec.kind not in ("allreduce", "broadcast"):
                return None
            if any(t.sigs is None for t in rec.templates):
                return None
            if (rec.spec.svc is None) != (svc is None) \
                    or (svc is not None and rec.spec.svc is not svc):
                return None
        if svc is not None:
            # A user name repeated WITHIN the step needs the eager
            # path's name-reuse serialization (two sequential
            # negotiation batches); replay's single negotiate_step round
            # would orphan the first request and stall — such a stream
            # is uncapturable, not replayable-with-a-hang.
            names = [n for rec in records for t in rec.templates
                     for n in t.names]
            if len(names) != len(set(names)):
                return None
        if svc is None:
            run_step, nbytes = _make_jit_execute(records)
        else:
            run_step, nbytes = _make_svc_execute(records)
        return StepPlan(key, records, run_step, nbytes, len(records),
                        rebindable=svc is not None and all(
                            getattr(r.spec.pset, "is_global", False)
                            for r in records))

    def _arm_locked(self, plan: StepPlan) -> None:
        self._plan = plan
        self._expect = {}
        self._held = {}
        self._matched = 0
        self._total = 0
        for ri, rec in enumerate(plan.records):
            for ei, tmpl in enumerate(rec.templates):
                seq = self._expect.setdefault(
                    tmpl.key, {"templates": [], "pos": 0})
                seq["templates"].append((ri, ei, tmpl))
                self._total += 1
        self._state = "replay"
        self._replaying = True

    # -- recording ---------------------------------------------------------

    def note_flush(self, spec, entries, trigger) -> None:
        """Record one drained flush's composition (record mode only; the
        flush still executes eagerly through its normal path)."""
        if not self._recording:
            return
        with self._mu:
            if self._state != "record":
                return
            templates = [
                _EntryTemplate(e.queue_key, e.grouped, e.count,
                               getattr(e, "sigs", None), e.names)
                for e in entries
            ]
            # capturability (kinds, sigs, svc homogeneity, name
            # uniqueness) is decided once at seal by _build_plan — the
            # recording just captures composition
            self._records.append(_FlushRecord(spec, templates, trigger))

    # -- replay ------------------------------------------------------------

    def offer(self, key, spec, entry) -> bool:
        """Replay-mode submission intake: match the entry against the
        recorded stream and hold it for the captured program. Returns
        True when consumed; False sends the entry down the normal queue
        path (replay off, or this submission just diverged)."""
        del spec
        if not self._replaying:
            return False
        run = plan = None
        fallback = None
        diverged = False
        with self._mu:
            if self._state != "replay":
                return False
            seq = self._expect.get(key)
            tmpl = None
            if seq is not None and seq["pos"] < len(seq["templates"]):
                ri, ei, tmpl = seq["templates"][seq["pos"]]
            if tmpl is None or not tmpl.matches(entry):
                # shape/dtype drift, a new tensor, or a different
                # composition: invalidate and fall back to eager
                fallback = self._take_held_locked()
                self._diverge_locked()
                diverged = True
            else:
                seq["pos"] += 1
                entry.captured = True
                self._held[(ri, ei)] = entry
                self._matched += 1
                if self._matched == self._total:
                    plan = self._plan
                    run = self._take_held_locked()
                    self._state = "replayed"
                    self._replaying = False
        if diverged:
            if fallback:
                self._run_fallback(fallback)
            return False
        if run is not None:
            self._execute_replay(plan, run)
        return True

    def _take_held_locked(self) -> list:
        """Held entries grouped per record in stream order (partial
        groups when taken mid-stream for a fallback)."""
        held, self._held = self._held, {}
        plan = self._plan
        if not held or plan is None:
            return []
        groups = []
        for ri, rec in enumerate(plan.records):
            es = [held[(ri, ei)] for ei in range(len(rec.templates))
                  if (ri, ei) in held]
            if es:
                groups.append((rec, es))
        return groups

    def _diverge_locked(self) -> None:
        # Lockstep decision point (docs/conformance.md): a divergence
        # fallback is itself rank-deterministic — the stream mismatched
        # identically everywhere (a rank-local fallback IS a finding).
        _conformance.record(
            "ops/step_capture.py::CaptureState._diverge_locked", "phase",
            (self._state, "bypass"))
        self._stats["fallbacks"] += 1
        self._stats["invalidations"] += 1
        _note_capture("fallback", state="bypass")
        _note_capture("invalidated")
        self._plan = None
        self._last_key = None
        self._expect = {}
        self._matched = self._total = 0
        self._state = "bypass"
        self._replaying = False

    def _run_fallback(self, groups) -> None:
        """Execute held entries eagerly with their recorded composition
        (the transparent-fallback contract: correct results, no hang)."""
        _timeline.record_capture("FALLBACK")
        svc_names = {n for _rec, es in groups for e in es
                     if e.requests for n in e.names}
        if svc_names:
            # same cross-step name-reuse guard the replay path applies:
            # an earlier step's pipelined flush may still hold one of
            # these names in an in-flight negotiation
            self._sched._wait_names_clear(svc_names)
        for i, (rec, es) in enumerate(groups):
            try:
                # _execute marks the entries failed itself on error, so
                # a bad flush surfaces at synchronize like any eager
                # flush; only non-Exception BaseExceptions escape it
                self._sched._execute(rec.spec, es)
            except BaseException as exc:
                # a KeyboardInterrupt/SystemExit mid-loop must not
                # orphan the remaining groups — they are out of _held
                # and out of every queue, so nothing else can ever
                # settle their waiters
                for _rec2, es2 in groups[i:]:
                    self._sched._fail_entries(es2, exc)
                raise

    def _execute_replay(self, plan: StepPlan, groups) -> None:
        """Issue the whole step's collective work as the one captured
        program and distribute results to the held entries."""
        entries = [e for _rec, es in groups for e in es]
        svc_names = {n for e in entries if e.requests for n in e.names}
        if svc_names:
            # Cross-step name reuse (a user name stable per call site):
            # an earlier step's pipelined flush may still hold the same
            # name in an in-flight negotiation — the eager path
            # serializes via this same guard, and skipping it would turn
            # the reuse into a DuplicateNameError from negotiate_step.
            self._sched._wait_names_clear(svc_names)
        try:
            # same re-entrancy section as every other dispatch path: a
            # collective enqueued from INSIDE the replay execution trips
            # enqueue's assert_outside under HVD_DEBUG_INVARIANTS
            # instead of silently corrupting composition
            with _inv.section("fusion-cycle-flush"), \
                    _REPLAY(), \
                    _dispatch.dispatch_source("step"):
                outs = plan.execute([es for _rec, es in groups])
            _dispatch.note_step_hit()
            if plan.nbytes:
                _autotune.record(plan.nbytes)
        except BaseException as exc:
            self._sched._fail_entries(entries, exc)
            hvd_logging.error("step replay failed: %s", exc)
            with self._mu:
                self._stats["invalidations"] += 1
                _note_capture("invalidated")
                self._plan = None
                self._last_key = None
            if not isinstance(exc, Exception):
                raise
            return
        for (rec, es), rec_outs in zip(groups, outs):
            i = 0
            for e in es:
                e.results = list(rec_outs[i:i + e.count])
                i += e.count
                e.tensors = ()
                e.run = None
                e.event.set()
        with self._mu:
            self._stats["replayed_steps"] += 1
            self._stats["replayed_entries"] += len(entries)
        # Lockstep decision point (docs/conformance.md): the replayed
        # whole-step program executed — same record count everywhere.
        _conformance.record(
            "ops/step_capture.py::CaptureState._execute_replay",
            "replayed", (len(groups),))
        _note_capture("replayed", state="replayed")
        _timeline.record_capture("REPLAY_DONE")

    # -- interception / teardown -------------------------------------------

    def intercept_flush(self, entry, trigger) -> bool:
        """A held entry's flush request. Dispatch hints (the bucketed
        optimizer's ``Handle.flush()``, threshold/cycle triggers) defer
        to the captured program — capture intentionally batches them. A
        BLOCKING observation (synchronize/poll) before the stream
        completed is a divergence: everything held executes eagerly so
        the caller can never hang on a dispatch that would only fire at
        stream completion."""
        if not getattr(entry, "captured", False) or entry.done:
            return False
        if trigger not in _BLOCKING_TRIGGERS:
            return True
        fallback = None
        with self._mu:
            if self._state == "replay" \
                    and any(e is entry for e in self._held.values()):
                fallback = self._take_held_locked()
                self._diverge_locked()
        if fallback:
            self._run_fallback(fallback)
        return True

    def flush_pending(self, trigger: str) -> None:
        """``flush_all`` (barrier/shutdown/backpressure) mid-replay: the
        caller needs everything *dispatched* on return, so the held
        prefix executes eagerly — divergence by early drain."""
        del trigger
        fallback = None
        with self._mu:
            if self._state == "replay" and self._held:
                fallback = self._take_held_locked()
                self._diverge_locked()
        if fallback:
            self._run_fallback(fallback)

    def abort(self, reason: str) -> int:
        """Scheduler abort (service reset, elastic re-form,
        ``PeerFailureError`` teardown): fail every held entry and drop
        both the recording and the armed plan — the world the capture
        was recorded against no longer exists. Returns the number of
        entries failed."""
        with self._mu:
            held = list(self._held.values())
            self._held = {}
            self._expect = {}
            self._records = []
            if (self._plan is not None or self._last_key is not None
                    or self._state in ("record", "replay")):
                self._stats["invalidations"] += 1
                _note_capture("invalidated")
            self._plan = None
            self._last_key = None
            self._matched = self._total = 0
            self._state = "bypass" if self._region_open else "idle"
            self._replaying = self._recording = False
        n = 0
        for e in held:
            if not e.done:
                e.error = RuntimeError(
                    f"captured collective {e.label!r} aborted: {reason}")
                e.tensors = ()
                e.run = None
                e.event.set()
                n += 1
        return n

    # -- stats -------------------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            out = dict(self._stats)
            out["enabled"] = self.enabled()
            out["state"] = self._state
            out["held_entries"] = len(self._held)
            out["armed"] = self._plan is not None
            return out

    def reset_stats(self) -> None:
        with self._mu:
            self._stats = {k: 0 for k in self._stats}


# ---------------------------------------------------------------------------
# public API (exported as hvd.step_marker)
# ---------------------------------------------------------------------------

class _Region:
    """Handle returned by :func:`step_marker`: usable bare (the call
    itself marked the boundary) or as a context manager closing the
    region on exit."""

    __slots__ = ("_cap",)

    def __init__(self, cap):
        self._cap = cap

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._cap.boundary(closing=True)
        return False


def step_marker() -> _Region:
    """Mark a training-step boundary for capture-and-replay
    (``HVD_STEP_CAPTURE``; docs/step_capture.md). Call once per loop
    iteration — each call seals/verifies the previous step region and
    opens the next — or use ``with hvd.step_marker():`` around the step
    body to close the region explicitly. A no-op (beyond closing an open
    region) while the knob is off."""
    from . import fusion_cycle
    cap = fusion_cycle.scheduler().capture
    cap.boundary()
    return _Region(cap)


class _AutoRegion:
    """The boundary pair ``optim.DistributedOptimizer`` wraps its eager
    bucketed gradient sync in: opens a capture region only when the knob
    is on and no user region is already active, so an explicit
    ``hvd.step_marker()`` spanning the whole step always wins."""

    __slots__ = ("_cap",)

    def __init__(self):
        self._cap = None

    def __enter__(self):
        from . import fusion_cycle
        cap = fusion_cycle.scheduler().capture
        if cap.enabled() and not cap.region_open():
            self._cap = cap
            cap.boundary()
        return self

    def __exit__(self, *exc):
        if self._cap is not None:
            self._cap.boundary(closing=True)
            self._cap = None
        return False


def auto_region() -> _AutoRegion:
    return _AutoRegion()
