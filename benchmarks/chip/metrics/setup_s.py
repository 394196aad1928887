"""Set-up seconds: process start to the window's start (JAX start-up,
graph generation, partitioning, the warm-up call and its compile or cache
load)."""


def read(run):
    return run.setup_s
