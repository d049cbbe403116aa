"""The loopback world fixture shared by the world>1 test modules.

``hvd.loopback.world(n)`` (docs/loopback.md) boots N ranks as threads in
ONE interpreter — real negotiation/elastic/watchdog protocol, emulated
collective execution. ``tests/test_loopback_world.py`` and the loopback
variants in the ``test_integration_*`` files run on it; the spawn-based
``hvdrun -np 2`` variants beside them execute real cross-process XLA
collectives on the CPU backend.
"""

import pytest


@pytest.fixture(params=[2, 4], ids=lambda n: f"world{n}")
def loopback_world(request):
    """A fresh loopback world per test, at N in {2, 4}. Import it into a
    test module (``from backend_markers import loopback_world``) and take
    it as a fixture argument."""
    import horovod_tpu as hvd
    with hvd.loopback.world(request.param) as w:
        yield w
