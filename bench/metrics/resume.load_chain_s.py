"""Seconds per resume in the program's ``recovery.load_chain`` span:
reading and checking the newest full and its differentials."""

SPAN = "recovery.load_chain"


def read(run):
    d = [e[5] - e[4] for e in run.spans if e[0] == SPAN]
    return sum(d) / len(d) if run.mode == "resume" and d else None
