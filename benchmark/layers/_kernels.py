"""Own time of named kernels in a device trace, for the readers that
report one kernel family each. A Mosaic (Pallas) call is a ``custom-call``
the compiler names after the jitted function around it
(``_flash_attend.1``) or, where many call sites share one, after the
module scope (``attn.12``); the compiler's own grouped product is a
custom call named for its kind (``ragged-dot-none.7``)."""

from benchmark import trace_reduce

CUSTOM_CALL = "custom-call"


def kernel_seconds(trace, names):
    """Seconds of own time, mean over the chips, of the custom calls whose
    name less its numeric suffix is one of ``names`` or one of them
    followed by ``-`` (``ragged-dot`` finds ``ragged-dot-none.7``);
    ``None`` where the trace holds none (the program has no such
    kernel)."""
    per_chip = []
    for chip in trace.chips:
        total = 0.0
        for label, seconds in trace_reduce.self_seconds(chip).items():
            name, _, rest = label.partition(" = ")
            stem = name.split(".")[0]
            if rest.endswith(CUSTOM_CALL) and any(
                    stem == n or stem.startswith(n + "-") for n in names):
                total += seconds
        per_chip.append(total)
    if not per_chip or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip)
