"""The one command, end to end, off the chip: under an explicit
``JAX_PLATFORMS=cpu`` a cell rehearses at its tiny sizes, prints no
result line and exits with the rehearsal's code; without it the run fails
at once."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.tests.conftest import ROOT


def run_cell(name, trace, env_changes, devices=1):
    env = {k: v for k, v in os.environ.items() if not k.startswith("HVD_")}
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(env_changes)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", name, "--seed",
         "3", "--seconds", "0.5", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("name,trace,devices", [
    ("resnet50-traced-1chip", 0, 1), ("resnet50-eager-1chip", 1, 1),
    ("gpt2m-gspmd-1chip", 1, 1), ("gpt2m-traced-4chip", 0, 4)])
def test_rehearsal_runs_every_stage_and_prints_no_result(name, trace,
                                                         devices):
    done = run_cell(name, trace, {"JAX_PLATFORMS": "cpu"}, devices)
    assert done.returncode == 3, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert lines[-1].startswith("[bench] REHEARSAL OK")
    with pytest.raises(json.JSONDecodeError):    # the last line: no result
        json.loads(lines[-1])
    shown = json.loads(next(
        l for l in lines if "rehearsal line" in l).split(": ", 1)[1])
    assert shown["correct"] and shown["failed"] == 0
    assert shown["checks"]["compiled_in_window"] == 0
    wanted = ({"init_s", "compile_s", "cache_hit_share", "host_enqueue_ms"}
              if trace else {"step_ms", "setup_s"})
    assert wanted <= set(shown["metrics"])


def test_off_the_chip_it_fails_without_a_result():
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("this machine has the chip")
    done = run_cell("resnet50-traced-1chip", 0, {})
    assert done.returncode not in (0, 3)
    assert "needs a TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_unknown_workload_is_refused():
    done = run_cell("no-such-cell", 0, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode not in (0, 3) and "no workload" in done.stderr
