"""Seconds from process start to the window: JAX start-up, data generation,
layout build, compile-cache loads and warm-up."""


def read(run):
    return run.setup_s
