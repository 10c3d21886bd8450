"""Plain jax.numpy references for GCN and GAT on edge lists.

Independent of the program: it imports nothing from it and is given
only the graph's edge list, features, labels and weights, all made by
``gen`` from the seed.  Aggregations are gather + ``segment_sum`` in
float32; dense products go through :func:`mm`, whose precision is an
argument:

* ``"highest"`` — float32 products (``lax.Precision.HIGHEST``): the
  reference.
* ``"high"`` — three bfloat16 passes (hi*hi + hi*lo + lo*hi), written
  out so that it is the same arithmetic on every backend: the control,
  one step below the float32-at-highest the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(a):
    """``a`` rounded to bfloat16's 8-bit significand, kept in float32:
    ``reduce_precision`` survives XLA's simplifier, where a round trip
    through a bfloat16 convert may be folded away."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dot3(a, b):
    """hi*hi + hi*lo + lo*hi of the bfloat16 halves, each product exact
    in a float32 (``highest``) dot and summed in float32."""
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.custom_vjp
def _mm_high(a, b):
    return _dot3(a, b)


def _mm_high_fwd(a, b):
    return _dot3(a, b), (a, b)


def _mm_high_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


_mm_high.defvjp(_mm_high_fwd, _mm_high_bwd)


def mm(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        return _mm_high(a, b)
    raise ValueError(f"unknown precision {precision!r}")


def gcn_edges(n: int, rows: np.ndarray, cols: np.ndarray):
    """Edges and weights of D^-1/2 (A + I) D^-1/2 for a 0/1 ``A`` given
    by its distinct (rows, cols); a self-loop already in ``A`` makes a
    diagonal weight of 2 before normalisation."""
    loop = np.arange(n, dtype=np.int32)
    r = np.concatenate([rows, loop])
    c = np.concatenate([cols, loop])
    deg = np.bincount(r, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(deg)
    w = (dinv[r] * dinv[c]).astype(np.float32)
    return r.astype(np.int32), c.astype(np.int32), w


def attention_edges(n: int, rows: np.ndarray, cols: np.ndarray):
    """The distinct nonzero pattern of A + I, the set GAT attends over."""
    loop = np.arange(n, dtype=np.int64)
    key = np.unique(np.concatenate([rows.astype(np.int64) * n + cols,
                                    loop * n + loop]))
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def gcn_forward(params, edges, x, precision: str):
    r, c, w = edges
    n = x.shape[0]
    h = x
    layers = len(params["w"])
    for i, wl in enumerate(params["w"]):
        h = mm(h, wl, precision)
        h = jax.ops.segment_sum(w[:, None] * h[c], r, num_segments=n)
        if i < layers - 1:
            h = jax.nn.relu(h)
    return h


def gat_forward(params, edges, x, precision: str):
    """Single-head GAT: e_ij = leaky_relu(a_src.h_i + a_dst.h_j, 0.2),
    softmax over row i's pattern, sum of alpha_ij h_j; ELU between
    layers."""
    r, c = edges
    n = x.shape[0]
    h = x
    layers = len(params["w"])
    for i, wl in enumerate(params["w"]):
        h = mm(h, wl, precision)
        s_src = mm(h, params["a_src"][i], precision)[:, 0]
        s_dst = mm(h, params["a_dst"][i], precision)[:, 0]
        e = jax.nn.leaky_relu(s_src[r] + s_dst[c], 0.2)
        mx = jax.ops.segment_max(e, r, num_segments=n)
        ex = jnp.exp(e - mx[r])
        den = jax.ops.segment_sum(ex, r, num_segments=n)
        alpha = ex / den[r]
        h = jax.ops.segment_sum(alpha[:, None] * h[c], r, num_segments=n)
        if i < layers - 1:
            h = jax.nn.elu(h)
    return h


FORWARD = {"gcn": gcn_forward, "gat": gat_forward}


def reference_edges(model: str, n: int, rows, cols):
    if model == "gcn":
        return tuple(jnp.asarray(a) for a in gcn_edges(n, rows, cols))
    return tuple(jnp.asarray(a) for a in attention_edges(n, rows, cols))


def nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1).mean()


def train_reference(model: str, params, edges, x, labels, lr: float,
                    steps: int, precision: str):
    """``steps`` SGD steps on the full graph.

    Returns (losses[steps], logits of step 1, gradient of step 1,
    params after ``steps``), all on the host.
    """
    fwd = FORWARD[model]

    @jax.jit
    def step(p):
        def loss_fn(p):
            logits = fwd(p, edges, x, precision)
            return nll(logits, labels), logits

        (loss, logits), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return p, loss, logits, g

    losses = []
    first = None
    for i in range(steps):
        params, loss, logits, g = step(params)
        losses.append(float(loss))
        if i == 0:
            first = (np.asarray(logits), jax.tree_util.tree_map(np.asarray, g))
    return (np.asarray(losses), first[0], first[1],
            jax.tree_util.tree_map(np.asarray, params))

