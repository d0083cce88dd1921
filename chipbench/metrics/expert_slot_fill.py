"""Share of the expert-capacity rows issued that hold a kept routed row:
(``moe_routed`` - ``moe_dropped``) / ``moe_slots``, from the program's
counters of the checked steps (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "expert_slot_fill")
