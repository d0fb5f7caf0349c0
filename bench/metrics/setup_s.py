"""From the start of the benchmark's process to the instant the last
set-up step's outputs are ready: JAX start, data, weights, compile or
cache load, and the warm-up steps."""


def read(run):
    return run.t0 - run.t_start
