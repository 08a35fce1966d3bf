"""Share of the chip's bf16 peak that the operations a round requires
(forward and backward, counted from the configuration's shapes by
``counts.train_flops_per_round``) take over the traced round time.  On a
TPU, float32 matmuls at the default precision run as one bf16 pass, so
the bf16 peak applies."""
from chipbench import counts


def read(ctx):
    t = ctx.trace
    per_chip = ctx.cell.traffic["learners"] / ctx.chips
    flops = counts.train_flops_per_round(ctx.cell.config, ctx.cell.traffic)
    seconds = t.window_s / len(t.rounds)
    return 100.0 * per_chip * flops / seconds / ctx.peaks["bf16_flops_per_s"]
