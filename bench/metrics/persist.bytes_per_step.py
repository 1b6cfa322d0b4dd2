"""Bytes the store wrote over the window, per window step (the store's
``bytes_written`` counter; the window's writes are flushed first)."""


def read(run):
    if run.mode != "train" or not run.steps or "bytes_written" not in \
            run.counters:
        return None
    return run.counters["bytes_written"] / run.steps
