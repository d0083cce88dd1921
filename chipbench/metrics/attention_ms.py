"""Device ms per traced step under the ``attention`` scope, forward,
recompute and backward (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "attention_ms")
