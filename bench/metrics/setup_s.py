"""Seconds from the start of the process to the start of the window: JAX
and the chip, the cell's trace, forest and traffic, compiling (or loading
from the cache) every shape, and the warm-up traffic (host clock)."""


def read(ctx):
    return ctx["setup_s"]
