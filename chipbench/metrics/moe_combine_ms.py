"""Device ms per traced step under the ``moe/combine`` scope: the gathers
out of the expert-capacity buffer and the weighted sum over the top-k
choices, forward, recompute and backward (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "moe_combine_ms")
