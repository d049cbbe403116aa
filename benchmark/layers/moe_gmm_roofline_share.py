"""Expert layer: the least time the chip could take for one step's
grouped matrix products - the larger of their operations over the peak
FLOP/s and their bytes over the HBM bandwidth, both counted from shapes at
the expected rows by ``models/lfm2.py`` (``gmm_flops``, ``gmm_bytes``:
the same whatever implements the products) - as a share (%) of the time
the device spent in them (``moe_gmm_ms``). Moves ``mfu``."""

import importlib

from benchmark import peaks
from benchmark.layers import moe_gmm_ms


def read(run):
    ms = moe_gmm_ms.read(run)
    mm = importlib.import_module(f"benchmark.models.{run.config['model']}")
    if ms is None or not hasattr(mm, "gmm_flops"):
        return None
    import jax

    kind = jax.devices()[0].device_kind
    tokens = run.cell["batch_per_chip"] * run.cell["seq_len"]
    least_s = max(mm.gmm_flops(run.config, tokens) / peaks.peak_flops(kind),
                  mm.gmm_bytes(run.config, tokens)
                  / peaks.peak_hbm_bytes_per_s(kind))
    return 100.0 * least_s / (ms * 1e-3)
