"""Share of the device's busy time in the traced window spent in leaf ops
under the step's ``attention`` scope: its norm, QKV, KV write and the walk
over the cached pages."""
import phases


def read(run):
    return phases.share(run, "attention")
