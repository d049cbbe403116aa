"""horovod_tpu: a TPU-native distributed training framework with Horovod's
capabilities (reference surveyed in SURVEY.md), built on jax/XLA.

Five-line usage, mirroring the reference README (``/root/reference/README.rst``):

    import horovod_tpu as hvd
    hvd.init()
    tx = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.size()))
    params = hvd.broadcast_parameters(params, root_rank=0)
    # train under jax.jit / shard_map over hvd.mesh()

Hot-path inversion (SURVEY.md §7): the reference injects a C++ background
runtime between the framework and NCCL/MPI; here the XLA compiler schedules
collectives natively over the ICI/DCN mesh. A native (C++) dynamic engine —
negotiation, response cache, fusion planning, stall inspection, Chrome-trace
timeline — is built on demand from ``native/`` and bound via ctypes
(:mod:`horovod_tpu.dynamic`); the eager collectives record into its
timeline (``hvd.start_timeline``).
"""

from . import runtime as _runtime
from .runtime import (
    AXIS_NAME,
    NotInitializedError,
    axis_name,
    ccl_built,
    cross_rank,
    cross_size,
    cuda_built,
    ddl_built,
    devices,
    gloo_built,
    gloo_enabled,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_ranks,
    local_size,
    mesh,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    process_count,
    process_rank,
    rank,
    rocm_built,
    shutdown,
    size,
    tpu_built,
    xla_built,
    xla_enabled,
)
from .ops import (
    Adasum,
    Average,
    Compression,
    Handle,
    Max,
    Min,
    PerRank,
    Product,
    ReduceOp,
    SparseRows,
    Sum,
    adasum_allreduce,
    allgather,
    allgather_async,
    allgather_object,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    broadcast_object,
    cached_step,
    dispatch_cache_stats,
    fusion_flush,
    fusion_stats,
    gspmd_cache_stats,
    grouped_allreduce,
    grouped_allreduce_async,
    grouped_broadcast,
    grouped_broadcast_async,
    hierarchical_allgather,
    hierarchical_allreduce,
    hierarchical_mesh,
    join,
    per_rank,
    poll,
    reducescatter,
    rows_from_dense,
    rows_to_dense,
    sparse_allreduce,
    sparse_allreduce_async,
    sparse_allreduce_to_dense,
    step_marker,
    synchronize,
)
from .process_sets import (
    ProcessSet,
    add_process_set,
    global_process_set,
    remove_process_set,
)
from .optim import (
    DistributedOptimizer,
    allreduce_gradients_transform,
    grad,
    value_and_grad,
)
from .functions import (
    broadcast_optimizer_state,
    broadcast_parameters,
    broadcast_variables,
)
from .exceptions import (
    HorovodInternalError,
    HostsUpdatedInterrupt,
    PeerFailureError,
    QosAdmissionError,
)
from . import qos
from .qos import QosClass, qos_stats, set_qos
from .health import health_stats
from .engine_service import response_cache_stats
from . import metrics
from .metrics import metrics_dump
from . import conformance
from .conformance import conformance_dump, conformance_stats
from .timeline import start_timeline, stop_timeline
from . import autotune
from . import callbacks
from . import checkpoint
from . import data
from . import elastic
from . import loopback
from . import parallel
from .parallel.mesh import (
    MeshLayout,
    MeshLayoutError,
    composed_mesh,
    mesh_layout,
    sync_gradients,
)
from .callbacks import average_metrics, metric_average
from .version import __version__


def __getattr__(name):
    # lazy: pulls in flax model definitions only when actually used, so
    # plain `import horovod_tpu` (launcher, runner utilities) stays light
    if name == "SyncBatchNorm":
        from .models.sync_batch_norm import SyncBatchNorm
        return SyncBatchNorm
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

# Torch-parity aliases (reference exposes in-place variants; jax arrays are
# immutable so they alias the pure versions).
allreduce_ = allreduce
broadcast_ = broadcast

__all__ = [
    "AXIS_NAME", "NotInitializedError", "axis_name", "cross_rank",
    "cross_size", "devices", "init", "is_homogeneous", "is_initialized",
    "local_rank", "local_ranks", "local_size", "mesh", "process_count",
    "process_rank", "rank", "shutdown", "size",
    "ccl_built", "cuda_built", "ddl_built", "gloo_built", "gloo_enabled",
    "mpi_built", "mpi_enabled", "mpi_threads_supported", "nccl_built",
    "rocm_built", "tpu_built", "xla_built", "xla_enabled",
    "Adasum", "Average", "Compression", "Handle", "Max", "Min", "PerRank",
    "Product", "ReduceOp", "Sum", "adasum_allreduce", "allgather",
    "allgather_async", "allgather_object", "allreduce", "allreduce_",
    "allreduce_async", "alltoall", "alltoall_async", "barrier", "broadcast",
    "broadcast_", "broadcast_async", "broadcast_object",
    "cached_step", "dispatch_cache_stats", "fusion_flush", "fusion_stats",
    "gspmd_cache_stats", "step_marker",
    "grouped_allreduce", "grouped_allreduce_async", "grouped_broadcast",
    "grouped_broadcast_async",
    "hierarchical_allgather", "hierarchical_allreduce", "hierarchical_mesh",
    "MeshLayout", "MeshLayoutError", "composed_mesh", "mesh_layout",
    "sync_gradients",
    "join", "per_rank", "poll", "reducescatter", "synchronize",
    "SparseRows", "rows_from_dense", "rows_to_dense", "sparse_allreduce", "sparse_allreduce_async",
    "sparse_allreduce_to_dense",
    "ProcessSet", "add_process_set", "global_process_set", "remove_process_set",
    "DistributedOptimizer", "allreduce_gradients_transform", "grad",
    "value_and_grad", "broadcast_optimizer_state", "broadcast_parameters",
    "broadcast_variables", "HorovodInternalError", "HostsUpdatedInterrupt",
    "PeerFailureError", "QosAdmissionError", "QosClass", "qos",
    "qos_stats", "set_qos", "health_stats", "response_cache_stats",
    "metrics", "metrics_dump",
    "conformance", "conformance_dump", "conformance_stats",
    "start_timeline", "stop_timeline", "autotune", "callbacks",
    "checkpoint", "data", "elastic", "loopback", "parallel",
    "average_metrics",
    "metric_average", "SyncBatchNorm", "__version__",
]
