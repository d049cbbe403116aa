"""CPU-side guards for the chip bring-up (chip_smoke.py, the benchmark's
refusal off the chip and its peak table, the compile-cache helper).
Everything here runs in seconds: what needs the chip is chip_smoke.py's
own job."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def test_chip_smoke_refuses_cpu():
    """No accelerator: non-zero exit, the platform named, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "platform: cpu" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_chip_smoke_result_line_is_the_contract():
    """The driver reads the last stdout line: exactly these keys."""
    import json

    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line(device)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    from horovod_tpu.utils import compile_cache

    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in knobs}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.place_compile_cache() == str(tmp_path)
        # jax reads the variable itself: no directory is set in code
        assert jax.config.jax_compilation_cache_dir == before[knobs[0]]

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = compile_cache.place_compile_cache()
        assert placed == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
        # sub-second programs (the eager path's) must be cached too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def test_peak_table_exact_key_or_error():
    from benchmark import peaks

    assert peaks.peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v5 litepod", "TPU", "TPU v9", "cpu"):
        with pytest.raises(ValueError, match="device_kind"):
            peaks.peak_flops(kind)


def test_benchmark_needs_tpu_or_explicit_cpu():
    """Nobody pinned the CPU and there is no chip: no result line."""
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this machine has the chip")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "resnet50-traced-1chip"], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert proc.returncode not in (0, 3)
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_flash_kernels_lower_for_tpu():
    """The three Pallas kernels still lower to one Mosaic custom call
    each at the smoke's shapes (lowering needs no chip; compiling them
    does)."""
    import chip_smoke

    for shape in chip_smoke.FLASH_SHAPES:
        for name, fn, specs in chip_smoke.flash_programs(shape):
            text = jax.jit(fn).trace(*specs).lower(
                lowering_platforms=("tpu",)).as_text()
            assert text.count("tpu_custom_call") == 1, (name, shape)


def test_flash_knob_is_loud_off_tpu(monkeypatch):
    from horovod_tpu.ops import flash

    monkeypatch.delenv("HVD_FLASH_ATTENTION", raising=False)
    monkeypatch.delenv("HOROVOD_FLASH_ATTENTION", raising=False)
    assert flash.supported() is False
    monkeypatch.setenv("HVD_FLASH_ATTENTION", "1")
    with pytest.raises(RuntimeError, match="HVD_FLASH_ATTENTION.*'cpu'"):
        flash.supported()
