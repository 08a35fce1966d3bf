"""Bytes each chip's collectives carry per window round: every collective
run in the trace (an async pair counted once, at its start), priced at
the array it reduces as the compiled program's HLO text in the trace
states it, averaged over chips.  A count, not a time."""
from chipbench.trace import is_collective, opcode, result_bytes


def read(ctx):
    t = ctx.trace
    total = 0
    for runs in t.op_runs:
        for name, n in runs.items():
            text = t.op_text[name]
            if is_collective(text) and not opcode(text).endswith("-done"):
                total += n * result_bytes(text)
    if total == 0:
        return None
    return total / t.chips / len(t.rounds)
