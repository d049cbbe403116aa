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
