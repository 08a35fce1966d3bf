"""A test metric read from a program counter: the router's balance loss
(``aux_loss`` in the round's metrics), mean over the traced rounds."""


def read(ctx):
    found = [c["aux_loss"] for c in ctx.counters.values() if "aux_loss" in c]
    return sum(found) / len(found) if found else None
