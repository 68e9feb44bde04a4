"""The window's ticks' share of the chip's roofline: for each tick the
larger of its operations over peak FLOP/s and its bytes over peak bytes/s
(the model family's ``tick_floor_s``), summed, over the ticks' host time."""


def read(run):
    ticks = run.window_ticks
    secs = sum(b - a for a, b, _, _ in ticks)
    if not secs:
        return None
    floor = sum(run.family.tick_floor_s(run.model, run.served, rows, run.peaks)
                for _, _, _, rows in ticks)
    return floor / secs * 100.0
