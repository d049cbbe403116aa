"""Parallelism schedules beyond the reference's data-parallel scope.

The reference implements data parallelism only (SURVEY.md §2.3); its
``alltoall`` primitive (``operations.cc:1642``) and Adasum's neighbor
exchanges are the building blocks long-context schedules need. This
package makes the schedules themselves first-class for TPU:

* :func:`ring_attention` — blockwise causal attention with KV blocks
  rotating over the mesh axis (``lax.ppermute`` ring, online-softmax
  accumulation): sequence length scales with the number of chips while
  attention memory stays at one block per chip.
* :func:`ulysses_attention` (+ the :func:`seq_to_heads`/:func:`heads_to_seq`
  all-to-all switches) — DeepSpeed-Ulysses-style sequence parallelism:
  resharding from sequence-parallel to head-parallel and back with two
  ``lax.all_to_all``\\ s, running exact full-sequence attention locally.
* :func:`moe_alltoall` (+ :func:`route_top_k`, :func:`load_balance_loss`)
  — expert parallelism: capacity-bounded top-k MoE dispatch/combine over
  one alltoall each way, one expert group per chip;
  :func:`moe_held_experts` (+ :func:`route_sigmoid_top_k`,
  :func:`grouped_matmul`) — a chip's share of experts that outnumber
  the chips: dropless, rows sorted by expert, grouped matrix products.
* :func:`pipeline_apply` — GPipe-style pipeline parallelism: one stage's
  params per chip, microbatches flowing around a ``ppermute`` ring inside
  one ``lax.scan`` (no host scheduler), optional stage rematerialization.
* :mod:`~horovod_tpu.parallel.mesh` — the composed-mesh layer that puts
  all of the above on ONE hierarchical device mesh (``dcn × ici_dp`` data
  axes + optional model axes carved from the ICI island) with the
  engine's gradient collectives reduced two-level over the data axes
  only (docs/mesh.md).
"""

from .mesh import (
    DATA_AXES,
    DCN_AXIS,
    ICI_DP_AXIS,
    MeshLayout,
    MeshLayoutError,
    composed_mesh,
    default_layout,
    layout,
    layout_signature,
    mesh_for_axes,
    mesh_layout,
    parse_axes,
    sync_gradients,
)
from .moe import (
    grouped_matmul,
    load_balance_loss,
    moe_alltoall,
    moe_held_experts,
    route_sigmoid_top_k,
    route_top_k,
)
from .pipeline import (
    microbatch,
    pipeline_apply,
    stack_stage_params,
    unstack_stage,
)
from .sequence import (
    heads_to_seq,
    ring_attention,
    seq_to_heads,
    ulysses_attention,
)

__all__ = ["ring_attention", "ulysses_attention", "seq_to_heads",
           "heads_to_seq", "pipeline_apply", "microbatch",
           "stack_stage_params", "unstack_stage",
           "moe_alltoall", "route_top_k",
           "load_balance_loss", "moe_held_experts",
           "route_sigmoid_top_k", "grouped_matmul",
           "DATA_AXES", "DCN_AXIS", "ICI_DP_AXIS",
           "MeshLayout", "MeshLayoutError", "composed_mesh",
           "default_layout", "layout", "layout_signature",
           "mesh_for_axes", "mesh_layout", "parse_axes",
           "sync_gradients"]
