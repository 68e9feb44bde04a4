"""Process start to the first measured tick: weights, pool, warm-up
compiles and the traffic's ramp."""


def read(run):
    return run.setup_s
