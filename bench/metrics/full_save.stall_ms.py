"""What a full save costs the training loop, on the benchmark's own step
clock: the mean wall of the steps that issued a full save and of the
step after each, minus the median wall of the other window steps."""
import statistics


def read(run):
    if run.mode != "train" or not run.full_steps:
        return None
    hit = set(run.full_steps) | {s + 1 for s in run.full_steps}
    walls = dict(zip(run.step_ids, run.step_walls))
    near = [w for s, w in walls.items() if s in hit]
    rest = [w for s, w in walls.items() if s not in hit]
    if not near or not rest:
        return None
    return 1e3 * (sum(near) / len(near) - statistics.median(rest))
