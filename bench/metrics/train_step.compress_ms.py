"""Device milliseconds per window step in the ops of the step's
``compress`` scope: the top-k selection of the gradient with its error
feedback (device trace, ``benchlib.scopes``)."""
from benchlib.scopes import step_ms


def read(run):
    return step_ms(run, "compress")
