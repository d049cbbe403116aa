"""Test harness: run the full suite on a virtual 8-device CPU mesh.

The analog of the reference's "multi-node without a cluster" strategy
(SURVEY.md §4): instead of spawning mpirun/horovodrun worker processes, we
give one process 8 XLA host devices (``--xla_force_host_platform_device_count``)
and treat each device as a rank.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _hvd_runtime():
    import horovod_tpu as hvd
    hvd.init(process_sets="dynamic")
    yield
    hvd.shutdown()


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    return hvd
