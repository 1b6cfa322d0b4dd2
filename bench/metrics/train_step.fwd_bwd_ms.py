"""Device milliseconds per window step in the ops of the step's
``fwd_bwd`` scope: the forward and backward passes (device trace,
``benchlib.scopes``)."""
from benchlib.scopes import step_ms


def read(run):
    return step_ms(run, "fwd_bwd")
