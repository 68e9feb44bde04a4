"""The window's ticks' share of the chip's bf16 peak: the operations their
work needs (counted from shapes by the model family's ``tick_flops``) over
the ticks' host time times peak FLOP/s."""


def read(run):
    ticks = run.window_ticks
    secs = sum(b - a for a, b, _, _ in ticks)
    if not secs:
        return None
    flops = sum(run.family.tick_flops(run.model, run.served, rows)
                for _, _, _, rows in ticks)
    return flops / (secs * run.peaks["bf16_flops"]) * 100.0
