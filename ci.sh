#!/usr/bin/env bash
# Safety checks a snapshot must pass before it ships: what tier-1 does not
# run. Speed is measured in one place, on the chip: benchmark/ (BENCHMARK.json,
# PERF.md); nothing here reads a clock.
#
# Mirrors the reference's pipeline structure (.buildkite/gen-pipeline.sh:
# unit suite + parallel multi-process jobs + example smoke runs), adapted to
# the TPU-native rebuild: everything runs on a virtual 8-device CPU mesh so
# no cluster (and no TPU) is required.
#
# Usage: ./ci.sh            # every check
#        ./ci.sh --fast     # stop after the suite, the sweeps and one example
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS=cpu
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

fail=0

step() { echo; echo "=== $* ==="; }

step "0 native build from source (no committed binaries)"
python -c "from horovod_tpu._native import build_native; print(build_native(force=True))"

step "0b native TSan lane (threaded engine under -fsanitize=thread; optional)"
# The native engine's real pthreads (timeline writer thread + the
# embedder's submitter/negotiator/watchdog threads) sit outside
# hvdsched's cooperative seam, so they get a ThreadSanitizer lane
# instead: native/tsan_harness.cc drives the documented hvd_core.h
# concurrency contract hard and asserts cross-rank response-list
# equality while it runs. Any data-race report fails the build. A
# toolchain without a working TSan runtime (probe below) skips with
# notice — the lane is additive coverage, not a portability gate.
CXX_BIN="${CXX:-g++}"
tsan_dir="$(mktemp -d)"
echo 'int main(){return 0;}' > "$tsan_dir/probe.cc"
if "$CXX_BIN" -fsanitize=thread -O1 -std=c++17 -pthread \
     "$tsan_dir/probe.cc" -o "$tsan_dir/probe" 2>/dev/null \
   && "$tsan_dir/probe" 2>/dev/null; then
  "$CXX_BIN" -fsanitize=thread -O1 -g -std=c++17 -pthread \
    native/tsan_harness.cc native/engine.cc native/timeline.cc \
    -o "$tsan_dir/tsan_harness"
  TSAN_OPTIONS="halt_on_error=1" \
    timeout -k 10 120 "$tsan_dir/tsan_harness" "$tsan_dir/timeline.json"
else
  echo "tsan lane: skipped (toolchain lacks a working -fsanitize=thread runtime)"
fi
rm -rf "$tsan_dir"

step "0a hvdlint static analysis gate (project invariants; docs/static_analysis.md)"
# AST-only, no jax import: the cheapest gate runs first. Any finding
# fails the build; the --json report (file/line/pass/message, per-pass
# timing) surfaces as structured CI annotations. --root tools lints the
# checkers themselves with the same passes.
lint_rc=0
lint_json="$(mktemp)"
python -m tools.hvdlint horovod_tpu --root tools --json > "$lint_json" || lint_rc=$?
# rc 0/1 = a report was emitted (clean/findings); anything else is an
# abnormal exit whose stderr is the real signal — don't bury it under a
# JSONDecodeError from an empty report file
if [ "$lint_rc" -le 1 ]; then
  LINT_JSON="$lint_json" python - <<'EOF'
import json, os
d = json.load(open(os.environ["LINT_JSON"]))
for f in d["findings"]:
    print("::error file=%s,line=%d,title=hvdlint/%s::%s"
          % (f["file"], f["line"], f["pass"], f["message"]))
timing = ", ".join("%s %.0fms" % (p["name"], p["seconds"] * 1e3)
                   for p in d["passes"])
state = "clean" if d["clean"] else "%d finding(s)" % len(d["findings"])
print("hvdlint: %s across %d files (%s)" % (state, d["files"], timing))
EOF
fi
rm -f "$lint_json"
[ "$lint_rc" -eq 0 ]

step "1 tier-1 selection (exit code only: the pass floor is the driver's)"
# faulthandler_timeout: a hung test (e.g. a flush-executor deadlock) dumps
# every thread's stack after 300 s instead of silently burning the budget.
timeout -k 10 1470 python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile -p no:randomly -o faulthandler_timeout=300

step "1j schedule-exploration gate (hvdsched race matrix; docs/schedule_checker.md)"
# Controlled-concurrency model checking of the fusion scheduler x flush
# executor x abort x watchdog x quiesce x QoS admission x elastic re-form
# x autoscale x checkpoint-snapshot race matrix, zero deadlock /
# lost-wakeup / livelock findings allowed. Then detector sanity: every
# planted known-bad demo must be FOUND. Any finding prints its (seed,
# trace) replay line on stderr.
# Starvation floor: explore() drives every clean model to its ceil-split
# budget, so runs < SCHED_MODEL_FLOOR means the registry outgrew
# --schedules and models are silently under-explored — raise the
# budget, don't shave the floor.
SCHED_MODEL_FLOOR="${SCHED_MODEL_FLOOR:-16}"
sched_rc=0
sched_json="$(mktemp)"
HVD_SCHED_CHECK=1 timeout -k 10 300 python -m tools.hvdsched --schedules 320 --json \
  > "$sched_json" || sched_rc=$?
# rc 0/1 = a report was emitted; anything else (timeout, crash) has its
# real signal on stderr
if [ "$sched_rc" -le 1 ]; then
  SCHED_JSON="$sched_json" SCHED_MODEL_FLOOR="$SCHED_MODEL_FLOOR" python - <<'EOF'
import json, os
d = json.load(open(os.environ["SCHED_JSON"]))
floor = int(os.environ["SCHED_MODEL_FLOOR"])
bad = [r["model"] for r in d["results"] if r["findings"]]
assert d["clean"] and not bad, "matrix findings in %r (replay on stderr)" % bad
starved = [(r["model"], r["runs"]) for r in d["results"]
           if r["runs"] < floor]
assert not starved, (
    "budget ceil-split starved model(s) under the %d-schedule floor: %r"
    " — the model registry outgrew --schedules 320" % (floor, starved))
print("sched matrix OK: %d models x %d schedules (floor %d), "
      "%d branched, %d pruned as equivalent, %d seed-swept" % (
          d["models"], d["per_model"], floor,
          sum(r["branch_points"] for r in d["results"]),
          sum(r["pruned"] for r in d["results"]),
          sum(r["swept"] for r in d["results"])))
EOF
fi
rm -f "$sched_json"
[ "$sched_rc" -eq 0 ]
HVD_SCHED_CHECK=1 timeout -k 10 300 python -m tools.hvdsched --demos --schedules 220

step "1l loopback chaos gate (world=4 rank death under HVD_DEBUG_INVARIANTS=1; docs/loopback.md)"
# An HVD_FAULT_SPEC rank death at world=4 must surface PeerFailureError on
# every survivor (watchdog silence detection over the shared KV), and a
# mid-elastic-run death must drive blacklist + re-form to a completed
# job. Runs with the concurrency witness on: a coordinated abort that
# corrupts lock order across the rank threads fails here.
env HVD_DEBUG_INVARIANTS=1 timeout -k 10 600 \
  python -m pytest tests/test_loopback_world.py::TestChaos -q \
    -o faulthandler_timeout=300

if [[ "${1:-}" == "--fast" ]]; then
  step "fast: examples/mnist.py (hvdrun -np 2) then exit"
  env -u XLA_FLAGS python -m horovod_tpu.runner.launch -np 2 -- \
    python examples/mnist.py --smoke
  echo "--fast: skipping second suite pass + invariant suites + dry run + full example checks"
  exit 0
fi

step "1b test suite, second pass (flake detection; runs the 'slow' tests too)"
python -m pytest tests/ -q -x -o faulthandler_timeout=300

step "1e concurrency invariant checker (threaded stress suites under HVD_DEBUG_INVARIANTS=1)"
# The dev-mode runtime checker (utils/invariants.py): lock-order witness,
# thread-affinity assertions, enqueue-reentrancy guard. The threaded
# stress tests must complete with zero invariant reports — a violation
# raises and fails the run.
env HVD_DEBUG_INVARIANTS=1 timeout -k 10 600 \
  python -m pytest tests/test_pipeline_flush.py tests/test_fusion_cycle.py \
    tests/test_invariants.py -q -o faulthandler_timeout=300

step "1f chaos gate (failure domain under HVD_DEBUG_INVARIANTS=1; docs/robustness.md)"
# Deterministic fault injection + watchdog + retry suite: injected KV
# flaps must be absorbed by the retry ladder, a simulated rank death
# must surface as PeerFailureError on the survivor with no hung waiter,
# and the elastic driver must blacklist + re-form on spawn failures and
# watchdog peer reports. Runs with the concurrency checker on: a
# coordinated abort that corrupts lock order fails here.
env HVD_DEBUG_INVARIANTS=1 timeout -k 10 600 \
  python -m pytest tests/test_faults.py -q -o faulthandler_timeout=120

step "2 driver artifact: single-chip compile check (entry)"
python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn).lower(*args).compile()
print("entry() compile OK")
EOF

step "3 driver artifact: multi-chip dryrun (8 virtual devices)"
python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

step "4 example smoke runs (single-process 8-dev mesh + np=2 hvdrun, like gen-pipeline.sh:160-290)"
for ex in examples/*.py; do
  echo "--- $ex (1 process, 8 virtual devices)"
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python "$ex" --smoke || fail=1
done
echo "--- examples/mnist.py (hvdrun -np 2)"
env -u XLA_FLAGS python -m horovod_tpu.runner.launch -np 2 -- \
  python examples/mnist.py --smoke || fail=1

exit $fail
