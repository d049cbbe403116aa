"""GSPMD cached-program fast path: stable step-signature caching for
jit/pjit train steps.

A GSPMD train step whose closure is re-created pays for *retracing*,
not for execution: ``jax.jit``'s internal cache keys on the **Python
identity** of the wrapped function, so the ubiquitous training-loop pattern of
re-creating the step closure (rebuilding a model wrapper, re-entering a
train function, re-forming after an elastic resize) pays the full
trace+lower+compile on every "first" call even though the program is
byte-identical. Every cache built since PR 1 (the dispatch plan cache,
PR-8 step capture) is eager-side only and never sees a GSPMD step.

:func:`cached_step` closes the gap by giving jit/pjit steps the same
"trace once, replay forever" contract the eager path already has:

* a stable **step signature** — pytree structure + leaf avals
  (shape/dtype/weak-type) + shardings + mesh identity + a content
  fingerprint of the step function (code object + primitive closure
  cells, never ``id()`` or weak function hashes) — keys a
  lowered+compiled executable in the dispatch plan cache
  (``ops/dispatch_cache.py``) under ``("gspmd", ..., sig)``, so every
  existing invalidation path (knob-override epoch, runtime generation,
  process-set removal, service reset, LRU pressure) applies unchanged;
* **donation** of parameter/optimizer buffers: ``donate_argnums`` is
  derived from the step's pytree layout (an argument donates when its
  leaf avals round-trip into the outputs — the params/opt-state carry),
  guarded by the PR-1 alias rules (an array object passed in two
  argument positions disqualifies both) and gated off on backends where
  donation is a no-op (``envs.donation_effective``);
* a capture-style **divergence contract**: shape/dtype/sharding drift
  simply produces a different signature (the cache holds several
  signatures, so train/eval shapes coexist); an executable that rejects
  its inputs *despite* a signature hit is dropped (:func:`~.dispatch_cache.drop`)
  and the call falls back to a plain traced ``jax.jit`` call — correct
  results, no hang, no stale-program reuse — then the next call
  re-records, mirroring ``ops/step_capture.py`` semantics.

GSPMD and eager DP converge on ONE cached-program architecture: the
dispatch plan cache is the shared store, :func:`~.dispatch_cache.fold_knobs`
the shared store-key canonicalizer, ``hits_by_source`` (now with a
``"gspmd"`` source) the shared hit accounting, and
:func:`~.step_capture._lifecycle_note` (with the capture phase
vocabulary) the shared metrics mirror. Loopback rank threads get
per-rank plan isolation for free through the dispatch cache's
per-context stores.

Contract (docs/gspmd.md): the step function must be *closure-light* —
anything that changes the compiled program must be visible in the
argument avals/shardings or captured as a primitive (str/int/float/
bool) closure cell. Capturing a mutable object whose state silently
changes the traced program (without changing any argument aval) is
outside the contract, exactly as it is for ``jax.jit`` itself when the
wrapper is reused.

Knobs: ``HVD_GSPMD_CACHE`` (default on; 0 restores plain per-call jit),
``HVD_GSPMD_CACHE_DONATE`` (auto|1|0; auto follows
``envs.donation_effective``). ``HVD_CACHE_CAPACITY=0`` disables this
cache along with every other dispatch plan.
"""

from __future__ import annotations

import collections

import jax

from .. import metrics as _metrics
from .. import timeline as _timeline
from ..utils import envs
from ..utils import logging as hvd_logging
from . import dispatch_cache as _dispatch
from . import step_capture as _capture
from .program_issue import issue_serialized as _issue_serialized

# Program spans (docs/timeline.md): the signature + plan lookup of every
# call, the trace / lower / compile (or cache fetch) / load of a new
# signature, and the replay (the Chrome timeline's GSPMD_STEP on the
# ``gspmd`` lane).
_LOOKUP = _timeline.span("cached_step.lookup")
_BUILD = _timeline.span("cached_step.build")
_RUN = _timeline.span("cached_step.run", "GSPMD_STEP", lane="gspmd")


# ---------------------------------------------------------------------------
# step-signature canonicalizer
# ---------------------------------------------------------------------------

def _mesh_fingerprint(mesh) -> tuple:
    """Stable identity of a device mesh: axis names, logical shape, and
    the physical device ids in mesh order. Two ``Mesh`` objects built
    over the same devices compare equal here even when the Python
    objects differ (the re-created-closure case); an elastic re-form
    that changes membership changes the id tuple and therefore the
    signature."""
    devices = getattr(mesh, "devices", None)
    if devices is not None:
        ids = tuple(int(d.id) for d in devices.flat)
        shape = tuple(devices.shape)
    else:  # AbstractMesh: no physical devices, shape is the identity
        ids = ()
        shape = tuple(getattr(mesh, "axis_sizes", ()) or ())
    return (tuple(getattr(mesh, "axis_names", ())), shape, ids)


def _sharding_fingerprint(leaf) -> tuple | None:
    """Canonical sharding component of a leaf signature. NamedShardings
    reduce to (mesh fingerprint, spec); anything else (single-device,
    GSPMD/positional shardings) keys on its repr, which jax keeps
    stable and content-descriptive. Uncommitted host values (numpy,
    scalars) carry no sharding."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return None
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is not None and spec is not None:
        # PartitionSpecs are rank-extended with trailing Nones; XLA strips
        # them on outputs (P('tp', None) comes back as P('tp')). Both mean
        # the same placement, so canonicalize by dropping the trailing
        # Nones — otherwise feeding step N's outputs into step N+1 would
        # spuriously miss.
        entries = list(tuple(spec))
        while entries and entries[-1] is None:
            entries.pop()
        return ("named", _mesh_fingerprint(mesh),
                tuple(str(p) for p in entries))
    return ("other", repr(sharding))


def leaf_signature(leaf) -> tuple:
    """(shape, dtype, weak_type, sharding) of one pytree leaf — THE
    shared per-leaf canonicalizer of the cached-program architecture:
    the step-capture templates canonicalize collective *stream* entries
    the same way (shape/dtype content, never object identity), and this
    is its aval-level twin for whole-step program arguments."""
    aval = jax.api_util.shaped_abstractify(leaf)
    return (tuple(aval.shape), str(aval.dtype),
            bool(getattr(aval, "weak_type", False)),
            _sharding_fingerprint(leaf))


def tree_signature(args: tuple) -> tuple:
    """Signature of an argument pytree: (treedef, per-leaf signatures).
    Treedefs hash structurally, so two structurally-identical pytrees
    built from different Python objects produce equal signatures."""
    flat, treedef = jax.tree.flatten(args)
    return (treedef, tuple(leaf_signature(leaf) for leaf in flat))


def _code_fingerprint(fn) -> tuple:
    """Content identity of the step function, stable across closure
    re-creation: module + qualname + the code object (CPython hashes
    code objects structurally, and a nested ``def`` re-executed by its
    builder reuses ONE code constant) + primitive closure cells. A
    non-primitive captured object contributes only its type, which is
    the documented closure-light contract: its *state* must show up in
    the argument avals, not in the trace."""
    code = getattr(fn, "__code__", None)
    cells = []
    for cell in (getattr(fn, "__closure__", None) or ()):
        contents = cell.cell_contents
        if isinstance(contents, (str, bytes, int, float, bool, type(None))):
            cells.append(("lit", contents))
        else:
            cells.append(("obj", type(contents).__module__,
                          type(contents).__qualname__))
    return (getattr(fn, "__module__", ""), getattr(fn, "__qualname__", ""),
            code, tuple(cells))


# ---------------------------------------------------------------------------
# donation derivation (the PR-1 alias-guard rules at step scope)
# ---------------------------------------------------------------------------

def _aliased_positions(args: tuple) -> set:
    """Argument positions sharing a leaf array *object* with another
    position: donating either would hand the executable a buffer the
    other position still reads (XLA rejects the call: ``f(donate(a),
    a)``). Both positions are excluded — the alias guard the per-flush
    dispatch plans apply to wire buffers, applied to step arguments."""
    by_id: dict = {}
    for i, arg in enumerate(args):
        for leaf in jax.tree.leaves(arg):
            if isinstance(leaf, jax.Array):
                by_id.setdefault(id(leaf), set()).add(i)
    return {i for positions in by_id.values() if len(positions) > 1
            for i in positions}


def _derive_donate_argnums(args: tuple, out_tree) -> tuple:
    """Donate the argument positions whose leaf avals round-trip into
    the outputs — the params/opt-state carry pattern: every donated
    buffer is replaced by a same-shaped output, so HBM is recycled
    instead of doubled. Output avals are *consumed* as arguments claim
    them, so two same-shaped arguments can never donate against one
    output slot; batch inputs (avals absent from the outputs) never
    donate."""
    out_counter = collections.Counter(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree.leaves(out_tree))
    aliased = _aliased_positions(args)
    donate = []
    for i, arg in enumerate(args):
        leaves = jax.tree.leaves(arg)
        if not leaves or i in aliased:
            continue
        if not all(isinstance(leaf, jax.Array) for leaf in leaves):
            continue
        claimed = collections.Counter(
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves)
        if all(out_counter[sig] >= n for sig, n in claimed.items()):
            out_counter -= claimed
            donate.append(i)
    return tuple(donate)


# ---------------------------------------------------------------------------
# the compiled-step constructor (hvdlint pass-5 donation seam)
# ---------------------------------------------------------------------------

def _gspmd_step_program(fn, args: tuple, donate=()):
    """Lower and compile ``fn`` for ``args``' exact signature, donating
    the ``donate`` positions, and wrap the executable in the program-
    issue lock (a replayed GSPMD step is a multi-device program enqueue
    like any eager collective). Registered in hvdlint pass 5
    (``donate-kwarg``): a local array passed in a donated position of
    the RESULT and read after the call is a read-after-donate finding —
    params/opt-state handed to a cached step belong to the step."""
    return _issue_serialized(
        jax.jit(fn, donate_argnums=tuple(donate)).lower(*args).compile())


class GspmdPlan(_dispatch.DispatchPlan):
    """A compiled GSPMD step in the dispatch plan cache. ``execute``
    holds the lock-wrapped executable; ``run`` replays it under the
    step's timeline lane. No ``negotiate`` stage and no payload
    accounting: the partitioner already owns cross-device movement, so
    the base class's negotiation-skip/autotune bookkeeping would count
    fictional work. Never shelved across elastic re-forms — the
    executable bakes the old world's device assignment
    (``dispatch_cache._restorable``)."""

    __slots__ = ("key", "donate_argnums")

    def __init__(self, key: tuple, execute, donate_argnums: tuple):
        super().__init__(_RUN.lane, _RUN.activity, None, None, execute,
                         variant="gspmd")
        self.key = key
        self.donate_argnums = donate_argnums

    def run(self, args: tuple):
        with _RUN():
            return self.execute(*args)


def _note_gspmd(event: str | None = None, state: str | None = None) -> None:
    """Registry mirror of the gspmd-cache lifecycle — the shared
    capture/gspmd instrument pattern (``step_capture._lifecycle_note``,
    same phase vocabulary)."""
    _capture._lifecycle_note(_metrics.GSPMD_CACHE_STEPS,
                             _metrics.GSPMD_CACHE_PHASE, event, state)


# ---------------------------------------------------------------------------
# the cached step
# ---------------------------------------------------------------------------

class CachedStep:
    """Callable wrapper around one step function (see
    :func:`cached_step`). Holds no compiled state itself — executables
    live in the dispatch plan cache, so two ``CachedStep`` objects over
    the same function (the re-created-closure pattern) serve each
    other's programs, and every cache-wide invalidation path applies."""

    def __init__(self, fn, donate="auto"):
        self._fn = fn
        self._donate = donate
        self._fingerprint = _code_fingerprint(fn)
        self._traces = 0
        self._counted = self._make_counted(fn)
        self._fallback = None

    @property
    def traces(self) -> int:
        """Times the step function has been traced through this wrapper
        (lowering, donation-shape probes, and plain-jit fallbacks all
        count) — the dryrun's regression evidence: a warm steady state
        replays with this number frozen."""
        return self._traces

    def _make_counted(self, fn):
        def _step(*args):
            self._traces += 1
            return fn(*args)
        return _step

    def _donate_tag(self) -> int:
        """Raw donation decision folded into the store key (the
        ``_store_key`` discipline: override-driven knob changes already
        invalidate via the cache epoch, but a raw env change does not
        bump the epoch — folding the resolved value means a program
        compiled under the other donation mode can never replay)."""
        if self._donate == "auto":
            return int(envs.gspmd_donate_enabled(jax.default_backend()))
        return 2  # explicit per-wrapper mask: keyed apart from both autos

    def _store_key(self, args: tuple) -> tuple:
        return _dispatch.fold_knobs(
            "gspmd", (self._fingerprint,) + tree_signature(args),
            self._donate_tag())

    def _resolve_donate(self, args: tuple) -> tuple:
        if self._donate == "auto":
            if not envs.gspmd_donate_enabled(jax.default_backend()):
                return ()
            return _derive_donate_argnums(
                args, jax.eval_shape(self._counted, *args))
        return tuple(self._donate or ())

    def _plain(self, args: tuple):
        """The divergence fallback: a plain traced call through one
        stable jit wrapper (jax's own cache keys on it, so repeated
        fallbacks of one signature retrace once). Mirrors the capture
        contract — correct results, no hang, no stale-program reuse."""
        if self._fallback is None:
            self._fallback = _issue_serialized(jax.jit(self._counted))
        return self._fallback(*args)

    def _build(self, args: tuple, key: tuple):
        donate = self._resolve_donate(args)
        try:
            program = _gspmd_step_program(self._counted, args,
                                          donate=donate)
        except (TypeError, ValueError) as exc:
            # Unlowerable under AOT (e.g. a signature the donation mask
            # mis-fits). Cache the negative decision so repeated calls
            # skip the rebuild attempt, then serve eagerly.
            hvd_logging.warning(
                "gspmd_cache: step is not AOT-compilable (%s); serving "
                "plain traced calls for this signature", exc)
            _dispatch.store(key, _dispatch.UNPLANNABLE)
            return None
        return GspmdPlan(key, program, donate)

    def __call__(self, *args):
        if not envs.gspmd_cache_enabled():
            _note_gspmd("bypass", state="bypass")
            return self._plain(args)
        # record_stats=False: like the capture controller, a hit counts
        # only when the replay actually SERVES (note_gspmd_hit below) —
        # an executable that rejects its inputs never counts.
        with _LOOKUP():
            key = self._store_key(args)
            plan = _dispatch.lookup(key, record_stats=False)
        if plan is _dispatch.UNPLANNABLE:
            return self._plain(args)
        if plan is not None:
            try:
                out = plan.run(args)
            except TypeError as exc:
                # Signature hit but the executable rejected the
                # arguments (aval/layout drift the signature cannot
                # see). Rejection happens before execution, so no
                # buffer was donated: drop the plan, serve this call
                # plainly, and let the next call re-record.
                hvd_logging.warning(
                    "gspmd_cache: cached executable rejected its inputs "
                    "(%s); invalidating and falling back to a traced "
                    "call", exc)
                _dispatch.drop(key)
                _note_gspmd("invalidated")
                _note_gspmd("fallback", state="bypass")
                return self._plain(args)
            _dispatch.note_gspmd_hit()
            _note_gspmd("replayed", state="replayed")
            return out
        _note_gspmd(state="record")
        with _BUILD():
            plan = self._build(args, key)
        if plan is None:
            _note_gspmd("fallback", state="bypass")
            return self._plain(args)
        _dispatch.store(key, plan)
        _note_gspmd("recorded")
        return plan.run(args)


def cached_step(fn, donate="auto") -> CachedStep:
    """Wrap a jit/pjit-style train step in the GSPMD cached-program
    fast path (docs/gspmd.md).

    ``cached = hvd.cached_step(train_step)`` then ``cached(params,
    opt_state, batch)``: the first call with a given signature lowers
    and compiles once; every later call with the same signature — from
    this wrapper or ANY other ``cached_step`` over the same function,
    including a re-created closure — replays the compiled executable
    with zero retrace. ``donate`` is ``"auto"`` (derive the
    params/opt-state donation mask per signature, off where donation is
    a backend no-op), an explicit tuple of argument positions, or
    ``()``/``None`` to disable donation."""
    return CachedStep(fn, donate=donate)


# ---------------------------------------------------------------------------
# DistributedOptimizer integration + stats
# ---------------------------------------------------------------------------

def note_passthrough() -> None:
    """Called by ``optim._allreduce_tree``'s GSPMD passthrough branch at
    trace time: counts gradient syncs routed through the partitioner
    (once per *trace*, not per step — a warm cached step holds this
    counter frozen, which is exactly the no-retrace evidence)."""
    _metrics.GSPMD_PASSTHROUGH_SYNCS.inc()


def stats() -> dict:
    """GSPMD cached-program counters (the ``hvd.gspmd_cache_stats()``
    API): a view over the shared registry instruments, shaped like the
    ``dispatch_cache_stats()``/capture blocks."""
    events = {}
    for labelitems, v in _metrics.GSPMD_CACHE_STEPS.series().items():
        events[dict(labelitems).get("event", "")] = int(v)
    cache = _dispatch.stats()
    return {
        "enabled": envs.gspmd_cache_enabled(),
        "hits": cache["hits_by_source"].get("gspmd", 0),
        "builds": cache["gspmd_builds"],
        "events": events,
        "passthrough_syncs": int(_metrics.GSPMD_PASSTHROUGH_SYNCS.value()),
    }


def reset_stats() -> None:
    for inst in (_metrics.GSPMD_CACHE_STEPS, _metrics.GSPMD_CACHE_PHASE,
                 _metrics.GSPMD_PASSTHROUGH_SYNCS):
        inst.reset()
