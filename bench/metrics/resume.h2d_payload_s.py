"""Seconds per resume in the program's ``replay.h2d`` span: checking
the chain's differentials and issuing their payloads' upload in wire
form (program span)."""

SPAN = "replay.h2d"


def read(run):
    d = [e[5] - e[4] for e in run.spans if e[0] == SPAN]
    return sum(d) / len(d) if run.mode == "resume" and d else None
