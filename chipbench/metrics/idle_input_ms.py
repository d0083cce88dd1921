"""Device idle ms per traced step while the host places the batch
(``train_loop/batch``), on the chip with most idle (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "idle_input_ms")
