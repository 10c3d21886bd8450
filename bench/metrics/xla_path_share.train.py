"""Share of the traced window the device spends in XLA-lowered sparse
products: ops whose innermost scope is ``sparse.xla.*`` (the csr gather +
segment-sum, the Block-COO scatter, the dense and reference paths)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "xla")
