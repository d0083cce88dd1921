"""Device ms per traced step under the ``moe/dispatch`` scope: the slot map
and the row gathers into the expert-capacity buffer, forward, recompute and
backward (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "moe_dispatch_ms")
