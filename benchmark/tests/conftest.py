"""Tests of the benchmark's own code. Run from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of tier-1 (``tests/``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name, tiny=False, **overrides):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    if tiny:
        config = {**config, **config["tiny"]}
    return {**config, **overrides}
