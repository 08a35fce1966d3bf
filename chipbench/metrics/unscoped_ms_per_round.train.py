"""Device time of the traced rounds under none of the program's scopes
(embedding, norms outside the scoped calls, the optimizer update, loop
overhead, the loader's small programs), ms per chip and window round.
With the scoped metrics it sums to the busy time per round."""
from chipbench import scopes


def read(ctx):
    return scopes.read(ctx, scopes.UNSCOPED)
