"""Share of the device's busy time in the traced window spent in leaf ops
under the step's ``lm_head`` scope: the final norm, the last-position
gather and the unembedding."""
import phases


def read(run):
    return phases.share(run, "lm_head")
