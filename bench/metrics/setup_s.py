"""Seconds from process start to the opening of the window: start-up,
data generation, load, build, indexes and warm-up."""


def read(run):
    return run.setup_s
