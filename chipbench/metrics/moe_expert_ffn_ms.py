"""Device ms per traced step under the ``moe/expert_ffn`` scope: the expert
FFN over the capacity buffer, forward, recompute and backward
(``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "moe_expert_ffn_ms")
