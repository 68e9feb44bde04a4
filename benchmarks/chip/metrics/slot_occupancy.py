"""Mean share of the engine's rows that held a request, over the window's
ticks, from the engine's own occupancy counter."""


def read(run):
    return None if run.occupancy is None else run.occupancy * 100.0
