"""Host milliseconds per window step in the program's
``engine.dispatch`` span: ``LowDiff.train_step`` handing the step
program to the device (program span)."""

SPAN = "engine.dispatch"


def read(run):
    d = [e[5] - e[4] for e in run.spans if e[0] == SPAN]
    return 1e3 * sum(d) / len(d) if run.mode == "train" and d else None
