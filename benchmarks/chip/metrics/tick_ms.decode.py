"""Mean host time of the window's decode-only ticks (ticks in which the
engine's mixed-tick counter did not grow)."""


def read(run):
    t = [b - a for a, b, mixed, _ in run.window_ticks if not mixed]
    return sum(t) / len(t) * 1e3 if t else None
