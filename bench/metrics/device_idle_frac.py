"""Device: share of the traced window in which no op ran on the chip,
``1 - busy / window``, busy being the union of the device ops' intervals."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
