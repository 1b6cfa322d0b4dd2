"""Milliseconds per window step that the persist thread blocked waiting
for full-snapshot D2H bytes (the program's CopyMeter ``d2h_wait_s``).
Only full saves feed it: None where no full save fell in the window."""


def read(run):
    if run.mode != "train" or not run.steps or not run.counters.get(
            "d2h_events"):
        return None
    return 1e3 * run.counters["d2h_wait_s"] / run.steps
