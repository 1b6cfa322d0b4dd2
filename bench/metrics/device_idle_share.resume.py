"""1 - device busy / window over the traced resumes, in percent
(device trace)."""


def read(run):
    t = run.trace_summary
    if run.mode != "resume" or t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
