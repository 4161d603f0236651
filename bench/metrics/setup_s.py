"""Seconds from process start to the first timed call: JAX start-up, the
cell's data, and the warm-up of every shape the window uses."""


def read(run):
    return run.setup_s
