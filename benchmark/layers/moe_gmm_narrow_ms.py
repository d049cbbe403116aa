"""Expert layer: milliseconds per step of the grouped matrix products' own
time on the device, forward and backward, in a cell whose experts are
narrow (width 768, 16 held): the ``ragged-dot`` custom calls, as
``moe_gmm_ms`` reads them (its list of cells cannot grow). Moves
``step_ms``."""

from benchmark.layers import moe_gmm_ms

read = moe_gmm_ms.read
