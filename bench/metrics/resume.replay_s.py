"""Seconds per resume in the program's ``recovery.replay`` span: staging
the differentials and replaying them on the device."""

SPAN = "recovery.replay"


def read(run):
    d = [e[5] - e[4] for e in run.spans if e[0] == SPAN]
    return sum(d) / len(d) if run.mode == "resume" and d else None
