"""The expert FFN's share of its roofline: the least time the chip needs for
the kept routed rows' work (``expert_ffn_least_s`` of the architecture's
module, per chip) over ``moe_expert_ffn_ms`` (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "expert_ffn_roofline")
