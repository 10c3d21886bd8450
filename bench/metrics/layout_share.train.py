"""Share of the traced window the device spends in layout work: ops whose
innermost scope is ``sparse.layout.*`` (the SELL tile-value gather,
permutes, pads, transposes), from each op's ``tf_op`` in the trace."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "layout")
