"""Device time under the program's ``mlp`` scope
(``models/mlp.mlp_apply``, forward and backward), ms per chip and window
round, as for ``attention_ms_per_round.train``."""
from chipbench import scopes

SCOPE = "mlp"


def read(ctx):
    return scopes.read(ctx, SCOPE)
