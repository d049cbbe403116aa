"""Runtime init / rank-query tests (reference analog:
``test/parallel/test_tensorflow.py`` rank/size tests and
``horovod/common/basics.py`` behavior)."""

import pytest


def test_initialized(hvd):
    assert hvd.is_initialized()
    assert hvd.size() == 8
    assert hvd.local_size() == 8  # single process drives all virtual chips
    assert hvd.rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.process_count() == 1
    assert hvd.is_homogeneous()


def test_mesh_shape(hvd):
    mesh = hvd.mesh()
    assert mesh.shape[hvd.axis_name()] == 8
    assert len(hvd.devices()) == 8


def test_double_init_is_noop(hvd):
    hvd.init()  # second call must not raise or reset state
    assert hvd.size() == 8


def test_uninitialized_raises():
    import horovod_tpu.runtime as rt
    saved = rt._state
    rt._state = None
    try:
        with pytest.raises(rt.NotInitializedError):
            rt.size()
    finally:
        rt._state = saved


def test_global_process_set(hvd):
    ps = hvd.global_process_set
    assert ps.process_set_id == 0
    assert ps.size() == 8
    assert ps.ranks == list(range(8))
    assert ps.included(3)
    assert ps.rank(5) == 5


def test_capability_queries():
    """Reference basics.py:273-371 migration shims: feature probes run
    unmodified; the single backend is XLA."""
    import horovod_tpu as hvd
    assert hvd.xla_built() and hvd.xla_enabled()
    assert hvd.mpi_threads_supported()
    assert not hvd.mpi_enabled() and not hvd.mpi_built()
    assert not hvd.gloo_enabled() and not hvd.gloo_built()
    assert not hvd.nccl_built() and not hvd.ddl_built()
    assert not hvd.ccl_built() and not hvd.cuda_built()
    assert not hvd.rocm_built()
    assert hvd.tpu_built() in (True, False)  # backend-dependent


def test_cluster_world_hint_requires_per_task_rank_var(monkeypatch):
    """`#SBATCH --ntasks=8` + plain `python` exports SLURM_NTASKS but no
    SLURM_PROCID — init must NOT attempt a blocking multi-process join
    (code-review r4)."""
    from horovod_tpu import runtime as rt
    for wv, rv in rt._CLUSTER_ENV_PAIRS:
        monkeypatch.delenv(wv, raising=False)
        monkeypatch.delenv(rv, raising=False)
    assert rt._cluster_world_hint() == 1
    monkeypatch.setenv("SLURM_NTASKS", "8")
    assert rt._cluster_world_hint() == 1  # no SLURM_PROCID: batch script
    monkeypatch.setenv("SLURM_PROCID", "3")
    assert rt._cluster_world_hint() == 8  # inside an srun task
    monkeypatch.setenv("SLURM_NTASKS", "garbage")
    assert rt._cluster_world_hint() == 1


def test_failed_distributed_init_raises(monkeypatch):
    """A multi-process launch whose jax.distributed join fails must not
    carry on as an unsynchronised single-host world that exits 0."""
    import jax
    import pytest

    from horovod_tpu import runtime as rt

    def refuse(**kwargs):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    monkeypatch.setattr(rt, "_distributed_client_active", lambda: False)
    monkeypatch.setenv("HVD_COORDINATOR_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_NUM_PROCESSES", "2")
    monkeypatch.setenv("HVD_PROCESS_ID", "1")
    with pytest.raises(RuntimeError, match="process 1/2.*unreachable"):
        rt._maybe_distributed_init()

    # the scheduler-launched twin (srun / mpirun auto-detection)
    monkeypatch.delenv("HVD_COORDINATOR_ADDR")
    monkeypatch.delenv("HVD_NUM_PROCESSES")
    for wv, rv in rt._CLUSTER_ENV_PAIRS:
        monkeypatch.delenv(wv, raising=False)
        monkeypatch.delenv(rv, raising=False)
    monkeypatch.setenv("SLURM_NTASKS", "2")
    monkeypatch.setenv("SLURM_PROCID", "0")
    with pytest.raises(RuntimeError, match="auto-detection failed"):
        rt._maybe_distributed_init()
