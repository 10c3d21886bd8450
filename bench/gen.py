"""Seeded generators: the graph, weights, features and labels.

Everything a run feeds the program is made here from ``--seed``, so the
same seed gives the same inputs and no later change to the program can
move them.  ``skewed_graph`` is a copy of ``random_graph`` in the
program's ``data/pipeline.py`` (same draws in the same order); the
rest exists only here.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, named stream)."""
    tag = int.from_bytes(stream.encode(), "little") % (2 ** 63)
    return np.random.default_rng([int(seed) % (2 ** 64), tag])


def jax_key(seed: int, stream: str):
    import jax

    return jax.random.PRNGKey(
        int(rng_for(seed, stream).integers(0, 2 ** 31 - 1)))


def skewed_graph(n: int, avg_degree: float, rng: np.random.Generator,
                 pareto_shape: float = 2.0):
    """Directed edges (rows, cols), sorted and unique.

    Rows are drawn with Pareto(``pareto_shape``) weights, columns
    uniformly; ``avg_degree * n`` draws, duplicates collapse (a dense
    assignment in the original), self-loops allowed.
    """
    w = rng.pareto(pareto_shape, n) + 1.0
    w /= w.sum()
    draws = int(avg_degree * n)
    rows = rng.choice(n, size=draws, p=w)
    cols = rng.integers(0, n, size=draws)
    key = np.unique(rows.astype(np.int64) * n + cols)
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def dense_adjacency(n: int, rows, cols) -> np.ndarray:
    """The [n, n] float32 0/1 matrix the program's ``build_graph`` takes."""
    a = np.zeros((n, n), np.float32)
    a[rows, cols] = 1.0
    return a


def make_params(cfg: dict, key):
    """He-initialised float32 weights for ``cfg['model']``, on the device.

    GCN: {"w": [W_l]}; GAT adds per-layer attention vectors
    {"a_src": [.], "a_dst": [.]} of shape [d_out, 1].
    """
    import jax
    import jax.numpy as jnp

    from bench.work import layer_dims

    dims = layer_dims(cfg)
    n = cfg["n_layers"]
    ks = jax.random.split(key, 3 * n)

    def he(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * jnp.sqrt(2.0 / shape[0]).astype(jnp.float32))

    params = {"w": [he(ks[3 * i], (dims[i], dims[i + 1]))
                    for i in range(n)]}
    if cfg["model"] == "gat":
        params["a_src"] = [he(ks[3 * i + 1], (dims[i + 1], 1))
                           for i in range(n)]
        params["a_dst"] = [he(ks[3 * i + 2], (dims[i + 1], 1))
                           for i in range(n)]
    return params


def make_node_data(cfg: dict, n: int, key):
    """Features [n, in_features] ~ N(0, 1) and labels in [0, n_classes)."""
    import jax
    import jax.numpy as jnp

    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (n, cfg["in_features"]), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, cfg["n_classes"], jnp.int32)
    return x, y
