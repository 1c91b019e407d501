"""Seconds from the start of the process to the opening of the window:
weights, compilation or compile-cache loads, and warm-up traffic."""


def read(run):
    return run.setup_s
