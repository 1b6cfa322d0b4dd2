"""Device time of one execution of the jitted train step program
(``jit_step``), mean over the traced window (device trace)."""

PROGRAM = "jit_step"


def read(run):
    t = run.trace_summary
    if run.mode != "train" or t is None:
        return None
    runs = [d for name, ds in t["module_s"].items()
            if name.split("(")[0] == PROGRAM for d in ds]
    return 1e3 * sum(runs) / len(runs) if runs else None
