"""Launcher: JAX compilations (compiles or compile-cache loads) inside the
measured window.  Every program is to be ready before the window opens, so
this reads 0."""


def read(run):
    return run.compiles
