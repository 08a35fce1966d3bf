"""Device time of the collective operations (all-reduce, all-gather,
reduce-scatter, permutes) per chip and window round, in ms."""
from chipbench.trace import is_collective


def read(ctx):
    t = ctx.trace
    s = t.mean_op_seconds(is_collective)
    if s == 0.0:
        return None
    return 1e3 * s / len(t.rounds)
