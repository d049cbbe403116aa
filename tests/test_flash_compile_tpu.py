"""The blocked-attention kernels compiled by the TPU's own compiler for a
described (not attached) v5e, at the shape the benchmark's GPT-2 cells
run: Mosaic refuses here what it would refuse on the chip (an unaligned
tile, a transpose it cannot lay out, too much VMEM), at no chip time.
Nothing runs, so this says nothing about results or times
(``chip_smoke.py``'s ``flash_kernels`` phase does, on the chip).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and every xdist worker imports
every test file.
"""

import jax
import pytest

import chip_smoke

GPT2_CELLS = (64, 1024, 64)  # (batch 4 x 16 heads, sequence, head)
LFM2_CELL = (32, 8192, 64)   # one 8k sequence, 32 heads (8 kv repeated)
SDAR_CELL = (32, 4096, 128)  # a copy of one 4096-token sequence, 32 heads


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", ["step", "whole", "bwd"])
def test_kernel_compiles_for_v5e_at_the_benchmark_shape(one_chip, name):
    programs = {n: (fn, specs) for n, fn, specs
                in chip_smoke.flash_programs(GPT2_CELLS)}
    fn, specs = programs[name]
    specs = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                  for s in specs)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# ``bwd`` keeps one (batch x head)'s whole dq in VMEM: 8192 rows is the
# longest sequence a cell runs it at
@pytest.mark.parametrize("name", ["whole", "bwd"])
def test_kernel_compiles_for_v5e_at_the_8k_cell(one_chip, name):
    programs = {n: (fn, specs) for n, fn, specs
                in chip_smoke.flash_programs(LFM2_CELL)}
    fn, specs = programs[name]
    specs = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                  for s in specs)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# the block-diffusion cell: heads of 128 (dq of one head: 2 MiB in VMEM),
# under the two limits its sweeps run, one with a block that is no power
# of two (the limit divides positions by it)
@pytest.mark.parametrize("limit", [("block_causal", 4), ("earlier_blocks", 4),
                                   ("block_causal", 6)],
                         ids=lambda limit: f"{limit[0]}-{limit[1]}")
def test_kernels_compile_for_v5e_under_the_block_limits(one_chip, limit):
    import jax.numpy as jnp

    from horovod_tpu.ops import flash

    limit = getattr(flash, limit[0])(limit[1])
    bh, s, d = SDAR_CELL
    qkv = jax.ShapeDtypeStruct(SDAR_CELL, jnp.bfloat16, sharding=one_chip)
    col = jax.ShapeDtypeStruct((bh, s, 1), jnp.float32, sharding=one_chip)

    def whole(q, k, v):
        return flash.flash_attend(q, k, v, limit)

    def bwd(q, k, v, lse, dout, D):
        return flash.flash_block_grads(q, k, v, lse, dout, D, 0, 0, limit,
                                       out_dtype=jnp.bfloat16)

    for fn, specs in ((whole, (qkv,) * 3),
                      (bwd, (qkv, qkv, qkv, col, qkv, col))):
        compiled = jax.jit(fn).lower(*specs).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1


# (contraction, columns): w1 / w3 and w2 of the lfm2 cell's expert layers,
# 8 experts held, the buffer's 8192 x 4 rows
@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)])
def test_grouped_product_compiles_for_v5e_at_the_cell_shape(one_chip, k, n):
    """Forward, row gradient and weight gradient of ``parallel/moe.py``
    ``grouped_matmul``: the TPU compiler takes each ``lax.ragged_dot`` as
    a grouped kernel of its own (three ``ragged-dot`` custom calls), not
    as a dense product over the whole buffer a group."""
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe

    def loss(rows, weights, sizes):
        out = moe.grouped_matmul(rows, weights, sizes)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    specs = (jax.ShapeDtypeStruct((32768, k), jnp.bfloat16, sharding=one_chip),
             jax.ShapeDtypeStruct((8, k, n), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*specs).compile()
    assert compiled.as_text().count("%ragged-dot") >= 3


def test_held_experts_layer_compiles_for_v5e_with_no_pass_over_the_pairs(
        one_chip):
    """The lfm2 cell's expert layer, forward and backward (8192 tokens x
    top-4, 8 of 64 experts held, so chunks of 8192 rows): one loop each
    way for the chunks after the first, and neither the first chunk nor
    the loops hold an array as long as the 32768 pairs and as wide as
    the model or an expert."""
    import re

    import jax.numpy as jnp

    from horovod_tpu.parallel import moe

    def loss(x, logits, bias, w1, w3, w2):
        idx, weights = moe.route_sigmoid_top_k(logits, bias, 4)
        w1, w3, w2 = (w.astype(x.dtype) for w in (w1, w3, w2))

        def experts(rows, sizes):
            h = jax.nn.silu(moe.grouped_matmul(rows, w1, sizes)) \
                * moe.grouped_matmul(rows, w3, sizes)
            return moe.grouped_matmul(h, w2, sizes)

        y, load = moe.moe_held_experts(x, idx, weights, experts, first=0,
                                       count=8, n_routed=64)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), load

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    specs = (spec((8192, 2048), jnp.bfloat16), spec((8192, 64), jnp.float32),
             spec((64,), jnp.float32), spec((8, 2048, 1536), jnp.float32),
             spec((8, 2048, 1536), jnp.float32),
             spec((8, 1536, 2048), jnp.float32))
    assert moe.short_buffer_rows(32768, 8, 64) == 8192
    text = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 3, 4, 5), has_aux=True)).lower(
            *specs).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 2
    assert text.count("%ragged-dot") >= 9
    assert not re.findall(r"\[32768,(?:2048|1536)\]", text)


# (rows a step, d_model, query heads, head_dim) of the sdar and lfm2 cells,
# whose query heads select the flat statement, and of the GPT-2 cells
# under it all the same (16 heads of 64 select the other)
@pytest.mark.parametrize("rows,d_model,heads,head_dim", [
    (8192, 2048, 32, 128), (8192, 2048, 32, 64), (4096, 1024, 16, 64)])
def test_q_projection_gradient_compiles_for_v5e_as_a_plain_product(
        one_chip, rows, d_model, heads, head_dim):
    """The weight gradient of ``Attention``'s q projection stated over a
    flat view of the leaf, followed by Adam over the donated leaf (the
    step ``tools/projection_probe.py`` times): the TPU compiler keeps it a
    matmul and the results keep the leaf's shape.
    Stated over the three-dimensional leaf it lowers to a convolution
    with a window of ``heads`` taps (PERF.md section 6, PR 36)."""
    import jax.numpy as jnp
    import optax

    from tools import projection_probe as probe

    tx = optax.adam(3e-4)

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    leaf = jax.ShapeDtypeStruct((d_model, heads, head_dim), jnp.float32)
    specs = spec((jax.ShapeDtypeStruct((rows, d_model), jnp.bfloat16),
                  jax.ShapeDtypeStruct((rows, heads, head_dim), jnp.bfloat16),
                  leaf, jax.eval_shape(tx.init, leaf)))

    def products(statement):
        step = probe.make_step(probe.weight_gradients()[statement], None, tx)
        kernel, (adam, _), _ = jax.eval_shape(step, *specs)
        assert kernel.shape == adam.mu.shape == adam.nu.shape == leaf.shape
        return [line for line in step.lower(*specs).compile().as_text()
                .splitlines() if " convolution(" in line]

    flat = products("flat")
    assert flat and not [line for line in flat if "window={size=" in line]
    # what the rule is about: should this stop holding, measure again
    assert [line for line in products("dense_general")
            if f"window={{size={heads} " in line]
