"""Tokens of every step completed in the window over the window's wall
time, with checkpointing as the cell configures it (host clock)."""


def read(run):
    if run.mode != "train" or not run.steps:
        return None
    return run.steps * run.tokens_per_step / run.window_s
