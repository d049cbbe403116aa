"""The benchmark's clocks: compile events, host spans, chained windows.

``CompileMeter`` is copied from ``chip_smoke.py`` (PERF.md lists the
original for deletion). A window is ``window_steps`` steps dispatched
back to back, each consuming the state the last one produced, ending in
one device sync: the chain serialises the steps on the device, so window
time over steps is the steady step time, and the one sync keeps the
host's round trip out of every step but the last.
"""

from __future__ import annotations

import contextlib
import math
import time

import jax

from benchmark.trace_reduce import SPAN_PREFIX

# Lowering and backend compilation (or the fetch from the persistent
# cache). Tracing is left out: its events nest, one per inner jit.
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Seconds jax spent lowering and compiling, how many programs
    reached the backend, and how many of those the persistent cache
    served."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in (_LOWER, _BACKEND):
            self.seconds += seconds
            self.programs += event == _BACKEND

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


class Spans:
    """Host spans of the benchmark's own calls into each layer. Each is
    also a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so a
    traced run has them on the profiler's clock beside the device, where
    ``trace_reduce`` finds them by the prefix."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name):
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.seconds[name] = (self.seconds.get(name, 0.0)
                                      + time.perf_counter() - start)


class Windows:
    """Runs chained windows over a ring of resident batches and keeps
    what each one measured."""

    def __init__(self, job, ring, window_steps, spans, meter):
        self.job, self.ring, self.window_steps = job, ring, window_steps
        self.spans, self.meter = spans, meter
        self.state = job.state
        self.steps = 0           # dispatched, ever: the ring's position
        self.first_loss = None   # the very first step's, still on device
        self.step_seconds = []   # per window: wall time / window_steps
        self.enqueue_seconds = []  # per step: host time for the call
        self.losses = []         # per window: the last step's loss
        self.failed = 0
        self.compiled_inside = 0

    def run_one(self):
        """One window. Returns how many programs were compiled in it."""
        programs = self.meter.programs
        enqueue = []
        start = time.perf_counter()
        with self.spans("window"):
            for _ in range(self.window_steps):
                batch = self.ring[self.steps % len(self.ring)]
                t0 = time.perf_counter()
                with self.spans("step_call"):
                    self.state, loss = self.job.step(self.state, batch)
                enqueue.append(time.perf_counter() - t0)
                if self.first_loss is None:
                    self.first_loss = loss
                self.steps += 1
            with self.spans("window_sync"):
                loss = float(jax.block_until_ready(loss))
        seconds = time.perf_counter() - start
        self.step_seconds.append(seconds / self.window_steps)
        self.enqueue_seconds.extend(enqueue)
        self.losses.append(loss)
        if not math.isfinite(loss):
            self.failed += self.window_steps
        compiled = self.meter.programs - programs
        self.compiled_inside += compiled
        return compiled

    def reset(self):
        """Forget the measurements (not the state): warm-up is over."""
        self.step_seconds, self.enqueue_seconds = [], []
        self.losses, self.failed, self.compiled_inside = [], 0, 0

    def run_for(self, seconds):
        """Whole windows until ``seconds`` have passed; returns the time
        they took."""
        start = time.perf_counter()
        while True:
            self.run_one()
            elapsed = time.perf_counter() - start
            if elapsed >= seconds:
                return elapsed
