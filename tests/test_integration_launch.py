"""End-to-end static launch integration test: real hvdrun spawning real
worker processes that rendezvous through jax.distributed on CPU — the
analog of the reference's ``test/integration/test_static_run.py`` (full
horovodrun on localhost).

``hvdrun --loopback`` runs the same worker contract as rank THREADS in
one interpreter (docs/loopback.md) and is exercised below too."""

import os
import subprocess
import sys
import textwrap


WORKER = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    import jax.numpy as jnp
    hvd.init()
    out = hvd.allreduce(jnp.ones(4) * (hvd.rank() + 1), op=hvd.Sum)
    gathered = hvd.allgather(jnp.array([float(hvd.rank())]))
    print("RESULT", hvd.rank(), hvd.size(), float(out[0]), gathered.tolist(),
          flush=True)
""")


def test_static_run_two_processes(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2", "--",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert proc.returncode == 0, proc.stderr
    lines = sorted(l for l in proc.stdout.splitlines() if "RESULT" in l)
    assert len(lines) == 2
    # 2 processes x 2 chips: world size 4; representative ranks 0 and 2.
    # p0 chips contribute 1.0 each, p1 chips contribute 3.0 each -> sum 8.
    assert "RESULT 0 4 8.0" in lines[0]
    assert "RESULT 2 4 8.0" in lines[1]


LOOPBACK_WORKER = textwrap.dedent("""\
    import sys
    import horovod_tpu as hvd
    import jax.numpy as jnp
    hvd.init()
    out = hvd.allreduce(jnp.ones(4) * (hvd.rank() + 1), op=hvd.Sum)
    gathered = hvd.allgather(jnp.array([float(hvd.rank())]))
    # rank threads share stdout (docs/loopback.md fidelity limits):
    # one write per line, or prints interleave
    sys.stdout.write("RESULT %d %d %s %s\\n" % (
        hvd.rank(), hvd.size(), float(out[0]), gathered.tolist()))
    sys.stdout.flush()
""")


def test_static_run_two_ranks_loopback(tmp_path):
    """The loopback port of the static launch test: one interpreter, two
    rank threads, real negotiation over the in-process KV."""
    script = tmp_path / "worker.py"
    script.write_text(LOOPBACK_WORKER)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "--loopback",
         "-np", "2", "--", sys.executable, str(script)],
        capture_output=True, text=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\n{proc.stderr}"
    lines = sorted(l for l in proc.stdout.splitlines() if "RESULT" in l)
    assert len(lines) == 2, proc.stdout
    # 2 rank threads, 1 chip each: world size 2; 1.0 + 2.0 -> 3.0
    assert "RESULT 0 2 3.0" in lines[0]
    assert "RESULT 1 2 3.0" in lines[1]
