"""The whole resume's share of the chip's peak: model FLOPs of the one
training step a resume ends with, over the mean resume time and the
peak bf16 FLOP/s, in percent. It bounds what the replay kernel's
roofline share can claim for ``resume_s``."""
from benchlib.flops import train_flops_per_token


def read(run):
    if run.mode != "resume" or not run.resume_times or run.peaks is None:
        return None
    mean = sum(run.resume_times) / len(run.resume_times)
    flops = train_flops_per_token(run.cfg) * run.tokens_per_step
    return 100.0 * flops / mean / run.peaks.flops
