"""GCN / GAT on the SpMM + SDDMM substrate — the paper's driving app.

GCN layer:   H' = act( Â (H W) )           — one SpMM per layer (paper §2.1);
             with ``fuse=True`` (default) the bias+act tail rides the
             SpMM's fused epilogue instead of a separate full pass.
GAT layer:   e = SDDMM(A, B, C) with d=2   — per paper §4.4, B/C hold source
             /destination attention scores; then segment-softmax over each
             row's edges and SpMM with the attention-weighted adjacency.
             With ``fuse=True`` (default) the whole chain runs as ONE
             ``fused_graph_attention`` dispatch (no E-length score vector
             materialized on the blocked paths).

The adjacency is one ``repro.sparse.SparseMatrix`` carrying both the
Block-ELL (MXU path) and element (scalar path) forms, so the dispatch
layer can route either path at jit trace time from the static stats the
matrix carries.  Both products run through the unified differentiable
front-end: training gradients flow through the custom_vjp rules where
SpMM's backward is SDDMM and vice versa.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.paper_gnn import GNNConfig
from repro.models.layers import _he
from repro.sparse import (SparseMatrix, fused_graph_attention, matmul,
                          sample)

# adjacency paths a Graph can execute (it carries ell + csr forms; the
# densified fallback is deliberately excluded from auto planning)
GRAPH_PATHS = ("ell", "sell", "csr")


def graph_candidates(adj: "SparseMatrix"):
    """Paths an adjacency's carried forms can execute (a bucketed batch
    pads only the planned form, so candidates must follow the forms)."""
    return tuple(p for p in GRAPH_PATHS
                 if (p == "csr" and adj.has_form("csr"))
                 or (p == "sell" and adj.has_form("sell"))
                 or (p == "ell" and (adj.has_form("ell")
                                     or adj.has_form("coo"))))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """Device-side graph: normalized adjacency as one ``SparseMatrix``.

    The matrix's ``stats`` are static aux metadata (plain Python
    numbers), so the dispatch layer can plan the SpMM path at jit trace
    time even though the adjacency arrays themselves are tracers.
    """
    adj: SparseMatrix
    n_nodes: int

    def tree_flatten(self):
        return (self.adj,), (self.n_nodes,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (adj,) = children
        return cls(adj=adj, n_nodes=aux[0])

    # -- legacy accessors (pre-SparseMatrix callers) ------------------------

    @property
    def ell(self):
        return self.adj.form("ell")

    @property
    def stats(self):
        return self.adj.stats if self.adj is not None else None

    @property
    def row_ids(self):
        return self.adj.form("csr")[0]

    @property
    def col_ids(self):
        return self.adj.form("csr")[1]

    @property
    def values(self):
        return self.adj.form("csr")[2]


def build_graph(adj_dense: np.ndarray, cfg: GNNConfig,
                normalize: bool = True) -> Graph:
    """adj_dense: [N, N] 0/1.  GCN normalization Â = D^-1/2 (A+I) D^-1/2.

    The ``gnn.build_graph`` span covers it all; its self time is the
    normalisation, its children the ``sparse.stats`` and
    ``sparse.pack.<form>`` spans of the packing."""
    with obs.span("gnn.build_graph"):
        n = adj_dense.shape[0]
        a = adj_dense.astype(np.float32)
        if normalize:
            a = a + np.eye(n, dtype=np.float32)
            deg = a.sum(1)
            dinv = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
            a = a * dinv[:, None] * dinv[None, :]
        formats = ("ell", "csr")
        adj = SparseMatrix.from_dense(a, formats=formats,
                                      block=(cfg.block_m, cfg.block_n))
        if adj.stats is not None and adj.stats.sparsity >= 0.99:
            # hyper-sparse adjacency: also pack SELL-C-σ so dispatch can
            # route around the Block-ELL padding cliff
            adj = adj.with_form("sell")
        return Graph(adj=adj, n_nodes=n)


def graph_spmm(graph: Graph, h, *, policy: str = "auto", epilogue=None,
               bias=None, residual=None):
    """One message-passing step A @ H, routed by the dispatch layer.

    The adjacency carries Block-ELL and element forms, so those are the
    candidate paths; the plan is made from the matrix's static stats and
    is therefore jit-trace safe (and memoized per graph instance).
    ``epilogue``/``bias``/``residual`` fuse the layer's elementwise tail
    into the aggregation (see ``repro.sparse.ops.matmul``).
    """
    if graph.adj is None or graph.adj.stats is None:
        raise ValueError(
            "graph_spmm: Graph adjacency has no sparsity stats; construct "
            "it with build_graph() (or SparseMatrix.from_dense) to use "
            "policy routing")
    cand = graph_candidates(graph.adj)
    return matmul(graph.adj, h, policy=policy,
                  candidates=cand or GRAPH_PATHS, epilogue=epilogue,
                  bias=bias, residual=residual)


# ---------------------------------------------------------------------------
# GCN
# ---------------------------------------------------------------------------


def init_gcn(key, cfg: GNNConfig, *, bias: bool = False) -> Dict:
    dims = [cfg.in_features] + [cfg.hidden] * (cfg.n_layers - 1) \
        + [cfg.n_classes]
    ks = jax.random.split(key, cfg.n_layers)
    params = {"w": [_he(ks[i], (dims[i], dims[i + 1]))
                    for i in range(cfg.n_layers)]}
    if bias:
        params["b"] = [jnp.zeros((dims[i + 1],), jnp.float32)
                       for i in range(cfg.n_layers)]
    return params


def gcn_forward(params, graph: Graph, x, *, use_blockell: bool = True,
                policy: Optional[str] = None, fuse: bool = True):
    """GCN forward pass.

    ``policy`` (when given) routes each layer's aggregation through the
    sparsity-adaptive dispatcher ("auto"/"ell"/"csr"); the legacy
    ``use_blockell`` flag forces the corresponding path otherwise.

    ``fuse=True`` (default) folds each layer's elementwise tail —
    per-layer bias (when the params carry ``"b"``) and the inter-layer
    relu — into the aggregation SpMM's epilogue, so the raw product
    never pays a separate full pass.  ``fuse=False`` keeps the unfused
    composition as the oracle.
    """
    if policy is None:
        policy = "ell" if use_blockell else "csr"
    biases = params.get("b")
    h = x
    n_layers = len(params["w"])
    for i, w in enumerate(params["w"]):
        with jax.named_scope(f"gnn.layer{i}"):
            h = h @ w
            b = biases[i] if biases is not None else None
            inner = i < n_layers - 1
            if fuse:
                h = graph_spmm(graph, h, policy=policy,
                               epilogue="relu" if inner else None, bias=b)
            else:
                h = graph_spmm(graph, h, policy=policy)
                if b is not None:
                    h = h + b
                if inner:
                    h = jax.nn.relu(h)
    return h


def batch_graphs(graphs) -> "Any":
    """Compose many Graphs' adjacencies block-diagonally.

    Returns a :class:`repro.batch.BatchedSparseMatrix`; wrap its
    ``.matrix`` in a Graph (or call :func:`gcn_forward_batched`) to run
    the whole batch through one planned aggregation per layer.
    """
    from repro.batch import BatchedSparseMatrix

    return BatchedSparseMatrix.from_matrices([g.adj for g in graphs])


def gcn_forward_batched(params, batch, hs, *, policy: str = "auto"):
    """GCN over N graphs at once via the block-diagonal composition.

    GCN weights are node-independent, so ``diag(A_1..A_N) @ (H W)``
    computes every graph's aggregation in one SpMM per layer.
    ``hs`` holds per-graph features [n_i, in_features]; returns the
    per-graph logits list.
    """
    h = batch.batch_features(hs)
    g = Graph(adj=batch.matrix, n_nodes=batch.matrix.shape[0])
    out = gcn_forward(params, g, h, policy=policy)
    return batch.unbatch(out)


# ---------------------------------------------------------------------------
# GAT (single head; attention scores via SDDMM with d=2, per the paper)
# ---------------------------------------------------------------------------


def init_gat(key, cfg: GNNConfig) -> Dict:
    dims = [cfg.in_features] + [cfg.hidden] * (cfg.n_layers - 1) \
        + [cfg.n_classes]
    ks = jax.random.split(key, 3 * cfg.n_layers)
    return {
        "w": [_he(ks[3 * i], (dims[i], dims[i + 1]))
              for i in range(cfg.n_layers)],
        "a_src": [_he(ks[3 * i + 1], (dims[i + 1], 1))
                  for i in range(cfg.n_layers)],
        "a_dst": [_he(ks[3 * i + 2], (dims[i + 1], 1))
                  for i in range(cfg.n_layers)],
    }


def _segment_softmax(scores, row_ids, n_rows):
    mx = jax.ops.segment_max(scores, row_ids, num_segments=n_rows)
    ex = jnp.exp(scores - mx[row_ids])
    den = jax.ops.segment_sum(ex, row_ids, num_segments=n_rows)
    return ex / jnp.maximum(den[row_ids], 1e-12)


def gat_forward(params, graph: Graph, x, *, policy: Optional[str] = None,
                fuse: bool = True):
    """GAT forward pass (single head, d=2 SDDMM scores per the paper).

    ``fuse=True`` (default) runs each layer's whole attention
    aggregation — SDDMM scores, leaky-relu, segment softmax, SpMM — as
    ONE planned ``fused_graph_attention`` dispatch over the adjacency's
    carried forms: a single plan per layer in the dispatch log, and no
    E-length score vector materialized on the blocked paths.

    ``fuse=False`` keeps the unfused three-dispatch composition as the
    oracle; it too now routes through the sparsity-adaptive dispatcher
    (``policy``, default "auto") instead of hand-forcing the csr path.
    """
    policy = "auto" if policy is None else policy
    h = x
    n = graph.n_nodes
    cand = graph_candidates(graph.adj) if fuse else None
    # 0/1 edge pattern in element form: the SDDMM sampling operand (the
    # attention scores ignore the normalized adjacency weights)
    patt = None if fuse else graph.adj.to("csr").pattern()
    for i, w in enumerate(params["w"]):
        with jax.named_scope(f"gnn.layer{i}"):
            h = h @ w
            with jax.named_scope("gnn.scores"):
                s_src = (h @ params["a_src"][i])[:, 0]  # [N]
                s_dst = (h @ params["a_dst"][i])[:, 0]
                # score factors with K=2 (paper §4.4): q=[s_src, 1],
                # k=[1, s_dst] so (q kᵀ)[i, j] = s_src[i] + s_dst[j]
                q = jnp.stack([s_src, jnp.ones_like(s_src)], axis=1)
            if fuse:
                k = jnp.stack([jnp.ones_like(s_dst), s_dst], axis=1)
                h = fused_graph_attention(graph.adj, q, k, h,
                                          edge_act="leaky_relu",
                                          negative_slope=0.2, policy=policy,
                                          candidates=cand or None)
            else:
                c = jnp.stack([jnp.ones_like(s_dst), s_dst], axis=0)
                e = sample(patt, q, c, policy=policy).data  # [nnz]
                e = jax.nn.leaky_relu(e, 0.2)
                alpha = _segment_softmax(e, graph.row_ids, n)
                h = matmul(patt.with_data(alpha), h, policy=policy)
            if i < len(params["w"]) - 1:
                h = jax.nn.elu(h)
    return h
