"""End-to-end GNN training — the paper's driving application.

Trains a 3-layer GCN (hidden 128, feature dim 256 — the paper's §4.1
setting) and a GAT (SDDMM attention with d=2 per §4.4) on a synthetic
random graph, full-batch, on whatever backend JAX finds.

Usage:  PYTHONPATH=src python examples/gnn_train.py [--kind gat] [--n 512]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.paper_gnn import CONFIG as GCFG
from repro.data.pipeline import random_graph
from repro.models.gnn import (build_graph, gat_forward, gcn_forward,
                              init_gat, init_gcn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="gcn", choices=("gcn", "gat"))
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()
    enable_compile_cache()

    rng = np.random.default_rng(0)
    adj = random_graph(args.n, avg_degree=8, seed=1)
    graph = build_graph(adj, GCFG)
    print(f"graph: {args.n} nodes, {int(adj.sum())} edges; "
          f"adjacency {graph.adj} "
          f"(Block-ELL occupancy {graph.ell.occupancy():.2f})")

    x = jnp.asarray(rng.normal(size=(args.n, GCFG.in_features))
                    .astype(np.float32))
    # planted community labels so the task is learnable
    labels = jnp.asarray((np.arange(args.n) * GCFG.n_classes // args.n)
                         .astype(np.int32))

    if args.kind == "gcn":
        params = init_gcn(jax.random.PRNGKey(0), GCFG)
        fwd = gcn_forward
    else:
        params = init_gat(jax.random.PRNGKey(0), GCFG)
        fwd = gat_forward

    def loss_fn(params):
        logits = fwd(params, graph, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], 1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return nll, acc

    @jax.jit
    def step(params):
        (l, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params = jax.tree_util.tree_map(
            lambda p, gg: p - args.lr * gg, params, g)
        return params, l, acc

    t0 = time.time()
    for i in range(args.steps):
        params, l, acc = step(params)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(l):.4f}  acc {float(acc):.3f}")
    print(f"{args.kind} trained {args.steps} steps in "
          f"{time.time() - t0:.1f}s")

    from repro.dispatch import last_plan
    from repro.sparse import plan_cache_stats
    plan = last_plan("spmm")
    print(f"aggregation dispatch: {plan.describe()}; "
          f"plan cache {plan_cache_stats()}")


if __name__ == "__main__":
    main()
