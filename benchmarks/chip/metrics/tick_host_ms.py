"""Mean host time of an engine tick that dispatched a step, less the wait
on the device's result: each ``engine.tick`` span in the traced window
less its ``engine.sync`` child."""


def read(run):
    p = getattr(run, "phases", None)
    t = [] if p is None else [
        k["s"] - k["phases"].get("engine.sync", 0.0) for k in p["ticks"]]
    return sum(t) / len(t) * 1e3 if t else None
