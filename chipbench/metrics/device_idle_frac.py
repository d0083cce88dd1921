"""1 - (union of device-operation intervals) / (traced window), per chip;
the chip with most idle."""

from chipbench import tracereduce


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * max(tracereduce.idle_share(run.trace).values())
