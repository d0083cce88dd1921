"""Device idle ms per traced step while the host waits on the loss, logs and
runs the hook (``train_loop/wait``, ``/log``, ``/hook``), on the chip with
most idle (``layers.readings``)."""

from chipbench import layers


def read(run):
    return layers.reading(run, "idle_loop_ms")
