"""Seconds per resume in the program's ``recovery.h2d_state`` span
(issue to landed: the full's params and both moments onto the device)
plus its ``recovery.h2d_ef`` span (issue only: the error feedback,
behind the replay) (program spans)."""

STATE, EF = "recovery.h2d_state", "recovery.h2d_ef"


def read(run):
    n = sum(1 for e in run.spans if e[0] == STATE)
    if run.mode != "resume" or not n:
        return None
    return sum(e[5] - e[4] for e in run.spans if e[0] in (STATE, EF)) / n
