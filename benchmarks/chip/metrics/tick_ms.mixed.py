"""Mean host time of the window's mixed ticks (a prompt chunk rode along)."""


def read(run):
    t = [b - a for a, b, mixed, _ in run.window_ticks if mixed]
    return sum(t) / len(t) * 1e3 if t else None
