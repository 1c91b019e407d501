"""Batching, in a backlog: the 95th percentile over every gap between
consecutive output tokens of one request, for tokens delivered inside the
window.  Above capacity it is a step's time plus the admissions before it,
so it swings with how many requests finish together; the backlog cells
report it here, beside their tokens per second."""

import numpy as np

from driver import itl_gaps


def read(run):
    gaps = itl_gaps(run.tracked, run.w0, run.w1)
    return float(np.percentile(gaps, 95)) if gaps.size else None
