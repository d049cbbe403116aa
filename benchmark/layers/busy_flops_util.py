"""Kernels: the model's operations per chip (``model_flops`` of the
configuration, as in ``mfu``) over the device's *busy* time per step, as
a share (%) of the chip's peak. Beside ``mfu``, which divides by the whole
step, it says whether a low utilization is slow kernels (this is low too)
or an idle chip (this is high). Moves ``mfu``."""

from benchmark import trace_reduce


def read(run):
    if run.trace is None or not run.peak_flops:
        return None
    busy_s, _ = trace_reduce.busy_and_window(run.trace)
    if busy_s <= 0:
        return None
    per_chip = run.model_flops / run.cell["chips"]
    return 100.0 * per_chip / (busy_s / run.traced_steps) / run.peak_flops
