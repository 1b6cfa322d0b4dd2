"""Device milliseconds per window step in the ops of the step's
``decompress`` scope: the dense gradient Adam reads, rebuilt from the
compressed one (device trace, ``benchlib.scopes``)."""
from benchlib.scopes import step_ms


def read(run):
    return step_ms(run, "decompress")
