"""Share of the positions the step computed in the window that advanced no
row: 1 - the growth of the engine's ``positions_live`` counter over that
of its ``positions_computed``."""


def read(run):
    pos = getattr(run, "positions", None)
    if pos is None or not pos[1]:
        return None
    live, computed = pos
    return (1.0 - live / computed) * 100.0
