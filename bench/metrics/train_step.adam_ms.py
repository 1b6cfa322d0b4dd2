"""Device milliseconds per window step in the ops of the step's
``adam`` scope: the optimizer update of params and both moments
(device trace, ``benchlib.scopes``)."""
from benchlib.scopes import step_ms


def read(run):
    return step_ms(run, "adam")
