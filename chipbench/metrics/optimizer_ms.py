"""Device ms per traced step under the ``optimizer`` scope: the global-norm
clip and AdamW (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "optimizer_ms")
