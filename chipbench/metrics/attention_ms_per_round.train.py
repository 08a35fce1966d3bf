"""Device time under the program's ``attention`` scope
(``models/attention.gqa_attention``: projections, RoPE, the scores and
the output projection, forward and backward), ms per chip and window
round: each operation's self time, billed to the innermost scope its
``tf_op`` names (``scopes.py``)."""
from chipbench import scopes

SCOPE = "attention"


def read(ctx):
    return scopes.read(ctx, SCOPE)
