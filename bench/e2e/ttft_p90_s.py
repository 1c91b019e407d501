"""90th percentile over every request due inside the window of the time from
its due time to its first token.  A request with no token when the window
closes counts as (window end - due).  Failed requests are left out; they
count in ``failed``."""

import numpy as np


def read(run):
    waits = [
        (t.times[0] if t.times and t.times[0] <= run.w1 else run.w1) - t.due
        for t in run.tracked
        if run.w0 <= t.due <= run.w1 and not t.failed
    ]
    return float(np.percentile(waits, 90)) if waits else None
