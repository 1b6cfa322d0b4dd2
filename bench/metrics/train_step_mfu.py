"""Model FLOPs per token (benchlib.flops) times the run's own training
tokens per second, over the chip's peak bf16 FLOP/s, in percent."""
from benchlib.flops import train_flops_per_token


def read(run):
    if run.mode != "train" or not run.steps or run.peaks is None:
        return None
    rate = run.steps * run.tokens_per_step / run.window_s
    return 100.0 * train_flops_per_token(run.cfg) * rate / run.peaks.flops
