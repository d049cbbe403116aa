"""Compile cache: seconds jax spent lowering and compiling, or fetching
from the persistent cache, from process start to the end of warm-up
(``timing.CompileMeter``). Moves ``setup_s``."""


def read(run):
    return run.setup["compile_s"]
