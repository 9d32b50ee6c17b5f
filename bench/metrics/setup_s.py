"""Set-up time in seconds: from the start of the process to the start of
the measured window (loading, weights, compiling or loading every
program from the cache, warming every shape, and driving the traffic to
steady state)."""


def read(run):
    return run.setup_s
