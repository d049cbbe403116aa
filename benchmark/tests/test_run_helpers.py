"""The harness's small pieces that need no device."""

import importlib
import types

import pytest

from benchmark import peaks, run


class FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_peak_memory_is_live_buffers_plus_program_reservation():
    # the numbers one GPT-2 run printed on the chip (PR 22)
    chip = {"peak_bytes_in_use": 4972090880,
            "peak_bytes_reserved": 10260759552, "bytes_limit": 16909336064}
    emptier = {"peak_bytes_in_use": 1000, "peak_bytes_reserved": 2000}
    assert run.peak_memory_bytes(
        [FakeDevice(emptier), FakeDevice(chip)]) == 4972090880 + 10260759552
    # a backend that reports no reservation
    assert run.peak_memory_bytes(
        [FakeDevice({"peak_bytes_in_use": 7})]) == 7
    # the CPU keeps no statistics
    assert run.peak_memory_bytes([FakeDevice(None)]) is None


def test_process_age_is_small_and_positive():
    assert 0 < run.process_age_s() < 3600


def test_metrics_of_filters_by_cell(spec):
    names = {m["name"] for m in run.metrics_of(
        spec, "per_layer", "resnet50-eager-1chip")}
    assert {"buckets_per_step", "plan_hit_share", "init_s"} <= names
    assert not {"collective_ms", "gspmd_retraces"} & names
    assert len(run.metrics_of(spec, "end_to_end", "gpt2m-traced-4chip")) == 4


@pytest.mark.parametrize("window_steps,warm_max,clean,traced", [
    (8, 3, 1, 2), (1, 24, 8, 16), (2, 12, 4, 8), (5, 5, 2, 4)])
def test_lengths_in_steps_become_whole_windows(window_steps, warm_max,
                                               clean, traced):
    """Warm-up and traced lengths are fixed in steps; a cell's
    ``window_steps`` only decides how many windows hold them."""
    assert run.windows_for(run.WARMUP_STEPS_MAX, window_steps) == warm_max
    assert run.windows_for(run.WARMUP_CLEAN_STEPS, window_steps) == clean
    assert run.windows_for(run.TRACE_STEPS, window_steps) == traced
    assert run.windows_for(run.PLAIN_STEPS, window_steps) == traced


def test_unknown_device_kind_is_an_error():
    assert peaks.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peaks on record"):
        peaks.peak_flops("TPU v9 imaginary")


def test_counter_readers():
    def snapshot(buckets, hits, misses, builds):
        return {"fusion": {"flushes": {"bucket": buckets}},
                "dispatch": {"hits": hits, "misses": misses},
                "gspmd": {"builds": builds}}

    seen = types.SimpleNamespace(
        before=snapshot(4, 10, 2, 1), after=snapshot(36, 58, 2, 1),
        steps=16, retraces=0, enqueue_seconds=[0.002, 0.004, 0.003],
        spans={"init": 1.0, "broadcast_parameters": 8.5},
        setup={"compile_s": 14.0, "programs": 16, "cache_hits": 12})

    def read(name):
        return importlib.import_module(f"benchmark.layers.{name}").read(seen)

    assert read("buckets_per_step") == 2.0
    assert read("plan_hit_share") == 100.0
    assert read("gspmd_retraces") == 0
    assert read("host_enqueue_ms") == pytest.approx(3.0)
    assert read("init_s") == 9.5
    assert read("compile_s") == 14.0
    assert read("cache_hit_share") == 75.0
    seen.retraces = None          # a job whose wrapper counts no traces
    assert read("gspmd_retraces") is None


REPLICA_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
import horovod_tpu as hvd
from jax.sharding import NamedSharding, PartitionSpec as P
from benchmark import run
hvd.init()
everywhere = NamedSharding(hvd.mesh(), P())
same = jax.device_put({"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones(5)}, everywhere)
assert run.replicas_identical(hvd, same)
base = np.arange(12.0, dtype=np.float32).reshape(3, 4)
shards = [jax.device_put(base + (1e-7 if i == 2 else 0.0) * (base == 0), d)
          for i, d in enumerate(jax.devices())]
drifted = dict(same, w=jax.make_array_from_single_device_arrays(
    (3, 4), everywhere, shards))
assert not run.replicas_identical(hvd, drifted)
print("replicas ok")
"""


def test_replicas_identical_sees_one_drifted_chip():
    """On four virtual devices: equal replicas pass, and one element
    that differs on one chip fails."""
    import os
    import subprocess
    import sys

    from benchmark.tests.conftest import ROOT

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", REPLICA_SCRIPT], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "replicas ok" in done.stdout
