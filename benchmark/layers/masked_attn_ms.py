"""Kernels: milliseconds per step of the attention kernels' own time on
the device under a mask that is not causal by position (the
block-diffusion cell): every Mosaic call of ``ops/flash.py``, forward and
backward, whatever limit it runs under (``attn_kernel_ms`` reads the same
calls; its list of cells cannot grow). The few rows of ``jax.numpy`` that
add a noised row's own block are fusions, not in it. Moves ``step_ms``."""

from benchmark.layers import attn_kernel_ms

read = attn_kernel_ms.read
