"""Entry / launch: host seconds in ``import horovod_tpu`` (which loads or,
on a checkout's first run, builds what the program needs), placing the
compile cache, ``hvd.init()`` and ``hvd.broadcast_parameters``. Source:
the benchmark's host spans. Moves ``setup_s``."""


def read(run):
    spans = run.spans
    if "init" not in spans:
        return None
    return spans["init"] + spans.get("broadcast_parameters", 0.0)
