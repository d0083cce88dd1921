"""90th percentile, over the window's steps, of the interval from one
step's start to the next's (the last step's to the window's end), so host
work between steps counts."""

import numpy as np


def read(run):
    ends = list(run.step_starts[1:]) + [run.t_end]
    intervals = np.subtract(ends, run.step_starts)
    if len(intervals) == 0:
        return None
    return float(np.percentile(intervals, 90)) * 1e3
