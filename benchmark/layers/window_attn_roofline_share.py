"""Kernels: the least time the chip could take for one step's attention
in the window layers - its operations over the pairs the window lets
through only, forward and backward (``window_attn_flops`` of the model
module: the same whatever computes them, so a kernel that visits hidden
tiles earns nothing for it), over the peak FLOP/s; attention at these
shapes is bound by compute, not by bytes - as a share (%) of the time the
device spent in the window layers' attention (``window_attn_ms``). Moves
``mfu``."""

import importlib

from benchmark.layers import window_attn_ms


def read(run):
    ms = window_attn_ms.read(run)
    mm = importlib.import_module(f"benchmark.models.{run.config['model']}")
    if (ms is None or not run.peak_flops
            or not hasattr(mm, "window_attn_flops")):
        return None
    flops = mm.window_attn_flops(run.config, run.cell["batch_per_chip"],
                                 run.cell["seq_len"])
    return 100.0 * flops / run.peak_flops / (ms * 1e-3)
