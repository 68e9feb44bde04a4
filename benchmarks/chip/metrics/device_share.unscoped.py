"""Share of the device's busy time in the traced window spent in leaf ops
under no scope of the step: copies, argmax, the residual adds, cache
plumbing and any executable other than the steps."""
import phases


def read(run):
    return phases.share(run, None)
