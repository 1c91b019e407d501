"""Output tokens delivered to the host inside the window, over the window's
seconds.  Tokens of finished and unfinished requests both count."""

from driver import in_window


def read(run):
    n = sum(in_window(t.times, run.w0, run.w1) for t in run.tracked)
    return n / (run.w1 - run.w0)
