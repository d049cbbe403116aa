"""Expert layer: the least time the chip could take for one step's
grouped matrix products - the larger of their operations over the peak
FLOP/s and their bytes over the HBM bandwidth (``gmm_flops``,
``gmm_bytes`` of the model module) - as a share (%) of the time the
device spent in them (``moe_gmm_narrow_ms``). Counted at the rows the step
really runs, which the model module says (``rows_per_step``: a doubled
sequence is 2 x ``seq_len`` rows; ``moe_gmm_roofline_share`` counts
``seq_len``). Moves ``mfu``."""

import importlib

from benchmark import peaks
from benchmark.layers import moe_gmm_narrow_ms


def read(run):
    ms = moe_gmm_narrow_ms.read(run)
    mm = importlib.import_module(f"benchmark.models.{run.config['model']}")
    if ms is None or not hasattr(mm, "rows_per_step"):
        return None
    import jax

    kind = jax.devices()[0].device_kind
    rows = mm.rows_per_step(run.cell["batch_per_chip"], run.cell["seq_len"])
    least_s = max(mm.gmm_flops(run.config, rows) / peaks.peak_flops(kind),
                  mm.gmm_bytes(run.config, rows)
                  / peaks.peak_hbm_bytes_per_s(kind))
    return 100.0 * least_s / (ms * 1e-3)
