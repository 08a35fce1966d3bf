"""Device time under the program's ``ssm`` scope
(``models/mamba.mamba_apply``: projections, the causal conv and the
chunked selective scan, forward and backward), ms per chip and window
round, as for ``attention_ms_per_round.train``."""
from chipbench import scopes

SCOPE = "ssm"


def read(ctx):
    return scopes.read(ctx, SCOPE)
