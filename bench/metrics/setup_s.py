"""Seconds from process start to the first timed step: weights, engine,
compile or cache loads, warm-up."""


def read(run):
    return run.setup_s
