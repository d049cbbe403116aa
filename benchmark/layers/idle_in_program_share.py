"""Device: of the first chip's idle time in the traced window, the percent
that began while the calling thread was inside any ``hvd:`` span: how much
of the idle the program's own spans explain (the rest is the user's script
and the benchmark's loop). Where a cell's idle time is under 1 % of its
window this is a share of a small number (the window's one sync), and
still reported. Source: the program's spans against the device's
operations in the traced run (``program_spans.py``). Moves ``step_ms``."""

from benchmark import program_spans


def read(run):
    return program_spans.idle_in_program_share(run)
