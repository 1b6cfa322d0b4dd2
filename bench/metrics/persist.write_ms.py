"""Milliseconds of backend write time per window step (the store's
``write_time_s`` histogram sum)."""


def read(run):
    if run.mode != "train" or not run.steps or "write_time_s" not in \
            run.counters:
        return None
    return 1e3 * run.counters["write_time_s"] / run.steps
