"""Share of the traced window in the sparse backward rules' own ops:
those whose innermost scope is ``sparse.vjp.*`` (masks, cotangent
assembly, the attention softmax recompute), not the products the rules
dispatch, which carry their own scopes."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "vjp")
