"""The device scopes of the model zoo's layers (``timeline.scope``,
docs/timeline.md "Device scopes"), declared once here because several
modules write one layer's work: ``model.conv`` is ResNet's convolutions
and the transformer's gated short convolution, ``model.head`` the
logits of both."""

from .. import timeline as _timeline

STREAM = _timeline.scope("model.stream")
MLP = _timeline.scope("model.mlp")
CONV = _timeline.scope("model.conv")
BATCH_NORM = _timeline.scope("model.batch_norm")
POOL = _timeline.scope("model.pool")
EMBED = _timeline.scope("model.embed")
HEAD = _timeline.scope("model.head")
