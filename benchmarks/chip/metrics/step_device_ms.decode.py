"""Mean device time of one run of the ``decode_step`` executable (its event
on the trace's ``XLA Modules`` line), over runs inside the traced window."""


def read(run):
    p = getattr(run, "phases", None)
    return None if p is None else p["step_ms"].get("decode_step")
