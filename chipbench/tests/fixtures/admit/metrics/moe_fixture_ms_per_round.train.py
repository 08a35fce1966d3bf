"""A test metric: device time under a ``moe_fixture`` scope, ms per chip
and window round, billed as ``attention_ms_per_round.train`` bills
``attention``; the file's ``SCOPE`` alone makes ``scopes.py`` bill it."""
from chipbench import scopes

SCOPE = "moe_fixture"


def read(ctx):
    return scopes.read(ctx, SCOPE)
