"""End-to-end elastic integration test: a real elastic hvdrun job on
localhost whose discovery script grows the world mid-run, forcing the
existing worker to re-rendezvous in-process (jax world teardown + rebuild)
and the new worker to join and receive synced state.

The analog of the reference's ``test/integration/test_elastic_torch.py``
driven by ``elastic_common.py`` (scripted discovery whose output changes
as the job runs)."""

import json
import os
import subprocess
import sys
import textwrap


# Loopback twins of the spawn variants: the in-process driver in
# tests/test_loopback_world.py TestChaos/TestElastic, and the
# `hvdrun --loopback --min-np` CLI test below.


WORKER = textwrap.dedent("""\
    import json
    import os
    import sys
    import time

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import horovod_tpu as hvd

    TRIGGER = sys.argv[1]
    OUTFILE = sys.argv[2]
    TOTAL_STEPS = 60
    MAX_STEPS = 400  # bail-out when the resize never happens
    GROW_AT_STEP = 2

    hvd.init()
    state = hvd.elastic.JaxState(step=0, sizes=[])

    @hvd.elastic.run
    def train(state):
        # Run at least TOTAL_STEPS and until the grown world was observed,
        # so a slow discovery poll on a loaded machine cannot flake the test.
        while state.step < TOTAL_STEPS or \\
                (2 not in state.sizes and state.step < MAX_STEPS):
            # world size via a real collective: sum of ones over all chips
            out = hvd.allreduce(jnp.ones(2), op=hvd.Sum)
            world = int(float(out.reshape(-1)[0]))
            state.sizes = state.sizes + [world]
            state.step += 1
            if state.step == GROW_AT_STEP and hvd.rank() == 0:
                open(TRIGGER, "w").close()  # discovery now reports 2 slots
            time.sleep(0.2)
            state.commit()
        return state.sizes

    sizes = train(state)
    if hvd.rank() == 0:
        with open(OUTFILE, "w") as f:
            json.dump(sizes, f)
    print("ELASTIC-DONE", hvd.rank(), sizes, flush=True)
""")

DISCOVERY = textwrap.dedent("""\
    #!/bin/sh
    if [ -f {trigger} ]; then
        echo localhost:2
    else
        echo localhost:1
    fi
""")


def test_elastic_grow_world(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    trigger = tmp_path / "trigger"
    outfile = tmp_path / "sizes.json"
    discovery = tmp_path / "discover.sh"
    discovery.write_text(DISCOVERY.format(trigger=trigger))
    discovery.chmod(0o755)

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "1", "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", str(discovery),
         "--start-timeout", "120",
         "--", sys.executable, str(worker), str(trigger), str(outfile)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert outfile.exists(), proc.stdout
    sizes = json.load(open(outfile))
    # Started at world=1 (1 process x 1 chip), grew to world=2 after the
    # trigger; the committed step counter must not have gone backwards.
    assert len(sizes) >= 60
    assert sizes[0] == 1
    assert sizes[-1] == 2, sizes
    assert sorted(set(sizes)) == [1, 2]
    assert len(sizes) < 400, "world never grew; job hit the bail-out cap"


CRASH_WORKER = textwrap.dedent("""\
    import json
    import os
    import sys
    import time

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import horovod_tpu as hvd

    CRASH_MARK = sys.argv[1]
    OUTFILE = sys.argv[2]

    hvd.init()
    state = hvd.elastic.JaxState(step=0, sizes=[])

    @hvd.elastic.run
    def train(state):
        while state.step < 40:
            out = hvd.allreduce(jnp.ones(1), op=hvd.Sum)
            world = int(float(out.reshape(-1)[0]))
            state.sizes = state.sizes + [world]
            state.step += 1
            # The second worker kills itself once, mid-run: the survivor
            # must restore committed state and continue at world=1.
            if state.step == 10 and os.environ.get("HVD_RANK") == "1" \\
                    and not os.path.exists(CRASH_MARK):
                open(CRASH_MARK, "w").close()
                os._exit(1)
            time.sleep(0.15)
            state.commit()
        return state.sizes

    sizes = train(state)
    if hvd.rank() == 0:
        with open(OUTFILE, "w") as f:
            json.dump(sizes, f)
    print("SURVIVOR-DONE", hvd.rank(), len(sizes), flush=True)
""")

CRASH_DISCOVERY = textwrap.dedent("""\
    #!/bin/sh
    echo localhost:1
    echo 127.0.0.1:1
""")


def test_elastic_worker_crash_recovery(tmp_path):
    """A worker dies mid-run; the survivor restores its last commit,
    re-rendezvouses into a shrunken world, and finishes — the analog of the
    reference's elastic fault-injection tests (``elastic_common.py``).
    The two workers use distinct hostnames (localhost / 127.0.0.1) so
    blacklisting the crashed worker's host leaves the survivor's host
    available."""
    worker = tmp_path / "worker.py"
    worker.write_text(CRASH_WORKER)
    crash_mark = tmp_path / "crash.mark"
    outfile = tmp_path / "sizes.json"
    discovery = tmp_path / "discover.sh"
    discovery.write_text(CRASH_DISCOVERY)
    discovery.chmod(0o755)

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "2", "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", str(discovery),
         "--start-timeout", "120",
         "--", sys.executable, str(worker), str(crash_mark), str(outfile)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert outfile.exists(), proc.stdout
    sizes = json.load(open(outfile))
    # Job ran to completion: all 40 committed steps, starting at world=2
    # and ending at world=1 after the crash.
    assert len(sizes) >= 40
    assert sizes[0] == 2
    assert sizes[-1] == 1, sizes
    assert sorted(set(sizes)) == [1, 2]


LOOPBACK_WORKER = textwrap.dedent("""\
    import json
    import sys
    import time

    import jax.numpy as jnp
    import numpy as np
    import horovod_tpu as hvd

    TRIGGER = sys.argv[1]
    OUTFILE = sys.argv[2]

    hvd.init()
    state = hvd.elastic.JaxState(step=0, sizes=[])

    @hvd.elastic.run
    def train(state):
        while state.step < 15 or \\
                (2 not in state.sizes and state.step < 300):
            out = hvd.allreduce(jnp.ones(2), op=hvd.Sum)
            world = int(float(np.asarray(out).reshape(-1)[0]))
            state.sizes = state.sizes + [world]
            state.step += 1
            if state.step == 2 and hvd.rank() == 0:
                open(TRIGGER, "w").close()
            time.sleep(0.05)
            state.commit()
        return state.sizes

    sizes = train(state)
    if hvd.rank() == 0:
        with open(OUTFILE, "w") as f:
            json.dump(sizes, f)
    print("ELASTIC-DONE", hvd.rank(), len(sizes), flush=True)
""")


def test_elastic_grow_world_loopback(tmp_path):
    """The loopback CLI twin of test_elastic_grow_world: `hvdrun
    --loopback --min-np/--max-np` drives the REAL elastic driver over
    rank threads — the world grows 1 -> 2 mid-run on the CPU backend
    where the spawn variant must skip (docs/loopback.md)."""
    worker = tmp_path / "worker.py"
    worker.write_text(LOOPBACK_WORKER)
    trigger = tmp_path / "trigger"
    outfile = tmp_path / "sizes.json"
    discovery = tmp_path / "discover.sh"
    discovery.write_text(DISCOVERY.format(trigger=trigger))
    discovery.chmod(0o755)

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "--loopback",
         "-np", "1", "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", str(discovery),
         "--start-timeout", "120",
         "--", sys.executable, str(worker), str(trigger), str(outfile)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert outfile.exists(), proc.stdout
    sizes = json.load(open(outfile))
    assert sizes[0] == 1
    assert sizes[-1] == 2, sizes
    assert sorted(set(sizes)) == [1, 2]
    assert len(sizes) < 300, "world never grew; job hit the bail-out cap"
