"""Host milliseconds per window step that ``LowDiff.train_step`` blocked
handing the differential to the reusing queue (the program's
``queue_backpressure`` timeline charge)."""


def read(run):
    if run.mode != "train" or not run.steps or run.traffic["store"] is None:
        return None
    return 1e3 * run.counters["queue_backpressure_s"] / run.steps
