"""Kernels: milliseconds per step of the blocked attention kernels' own
time on the device (``ops/flash.py``: ``flash_attend`` forward,
``flash_block_grads`` backward; Mosaic calls the trace names ``attn.N``,
``_flash_attend.N`` or ``_flash_block_grads.N``). Moves ``step_ms``."""

from benchmark.layers import _kernels

KERNELS = ("attn", "_flash_attend", "_flash_block_grads")


def read(run):
    if run.trace is None:
        return None
    seconds = _kernels.kernel_seconds(run.trace, KERNELS)
    return None if seconds is None else seconds * 1e3 / run.traced_steps
