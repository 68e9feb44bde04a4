"""Share of the device's busy time in the traced window spent in leaf ops
under the step's ``ffn`` scope: its norm and the packed W4A16 FFN."""
import phases


def read(run):
    return phases.share(run, "ffn")
