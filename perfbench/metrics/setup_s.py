"""Seconds from the benchmark process's start to the window's start: JAX
start-up, weights, the server's construction, warm-up and the traffic's
ramp."""


def read(run):
    return run.window[0] - run.t_start
