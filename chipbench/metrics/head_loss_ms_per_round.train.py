"""Device time under the program's ``head_loss`` scope (the unembedding
and the softmax cross-entropy of ``models/transformer.py``'s
``loss_fn``, forward and backward; not the embedding lookup), ms per
chip and window round, as for ``attention_ms_per_round.train``."""
from chipbench import scopes

SCOPE = "head_loss"


def read(ctx):
    return scopes.read(ctx, SCOPE)
