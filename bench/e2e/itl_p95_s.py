"""95th percentile over every gap between consecutive output tokens of one
request, for tokens delivered inside the window."""

import numpy as np

from driver import itl_gaps


def read(run):
    gaps = itl_gaps(run.tracked, run.w0, run.w1)
    return float(np.percentile(gaps, 95)) if gaps.size else None
