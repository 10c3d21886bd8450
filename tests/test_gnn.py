"""GCN/GAT on the sparse substrate — the paper's application layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
from repro.data.pipeline import random_graph
from repro.models.gnn import (build_graph, gat_forward, gcn_forward,
                              init_gat, init_gcn)


@pytest.fixture
def graph(rng):
    adj = random_graph(48, avg_degree=4, seed=1, clustered=False)
    return build_graph(adj, GCFG)


def test_gcn_blockell_equals_csr_path(rng, graph):
    params = init_gcn(jax.random.PRNGKey(0), GCFG)
    x = jnp.asarray(rng.normal(size=(graph.n_nodes, GCFG.in_features))
                    .astype(np.float32))
    out_ell = gcn_forward(params, graph, x, policy="ell")
    out_csr = gcn_forward(params, graph, x, policy="csr")
    np.testing.assert_allclose(np.asarray(out_ell), np.asarray(out_csr),
                               rtol=2e-4, atol=2e-4)
    assert out_ell.shape == (graph.n_nodes, GCFG.n_classes)


def test_gcn_matches_dense_aggregation(rng, graph):
    params = init_gcn(jax.random.PRNGKey(0), GCFG)
    x = jnp.asarray(rng.normal(size=(graph.n_nodes, GCFG.in_features))
                    .astype(np.float32))
    a_hat = graph.adj.form("ell").to_dense()[:graph.n_nodes, :graph.n_nodes]
    h = np.asarray(x)
    for i, w in enumerate(params["w"]):
        h = a_hat @ (h @ np.asarray(w))
        if i < len(params["w"]) - 1:
            h = np.maximum(h, 0)
    out = gcn_forward(params, graph, x)
    np.testing.assert_allclose(np.asarray(out), h, rtol=2e-3, atol=2e-3)


def test_gat_rows_softmax_normalized(rng, graph):
    """Attention weights over each node's edges sum to 1 (post-softmax)."""
    params = init_gat(jax.random.PRNGKey(0), GCFG)
    x = jnp.asarray(rng.normal(size=(graph.n_nodes, GCFG.in_features))
                    .astype(np.float32))
    out = gat_forward(params, graph, x)
    assert out.shape == (graph.n_nodes, GCFG.n_classes)
    assert np.isfinite(np.asarray(out)).all()


def test_gnn_training_loss_decreases(rng, graph):
    """End-to-end: 30 steps of full-batch GCN training, planted signal."""
    params = init_gcn(jax.random.PRNGKey(0), GCFG)
    labels_np = (np.arange(graph.n_nodes) * GCFG.n_classes
                 // graph.n_nodes).astype(np.int32)
    feats = rng.normal(size=(graph.n_nodes, GCFG.in_features)) \
        .astype(np.float32)
    feats[:, : GCFG.n_classes] += 3.0 * np.eye(GCFG.n_classes)[labels_np]
    x = jnp.asarray(feats)
    labels = jnp.asarray(labels_np)

    def loss_fn(params):
        logits = gcn_forward(params, graph, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

    @jax.jit
    def step(params):
        l, g = jax.value_and_grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.3 * gg,
                                        params, g)
        return params, l

    losses = []
    for _ in range(60):
        params, l = step(params)
        losses.append(float(l))
    # full-batch GCN on a random graph learns slowly (neighbor averaging
    # dilutes the planted signal); monotone-ish descent is the invariant
    assert losses[-1] < losses[0] - 0.04, (losses[0], losses[-1])
