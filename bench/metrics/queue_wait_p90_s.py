"""Admission queue: 90th percentile, over the requests due inside the window,
of the time from a request's due time to the start of its first
``Engine.admit`` call (window end where it had none)."""

import numpy as np


def read(run):
    waits = [
        (t.admit_start if t.admit_start is not None and t.admit_start <= run.w1
         else run.w1) - t.due
        for t in run.tracked
        if run.w0 <= t.due <= run.w1 and not t.failed
    ]
    return float(np.percentile(waits, 90)) if waits else None
