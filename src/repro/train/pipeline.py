"""GPipe-style pipeline parallelism over a mesh axis.

The framework's default distribution is FSDP+TP(+pod-DP); at 1000+ node
scale an inter-pod *pipeline* axis trades the cross-pod gradient
all-reduce for point-to-point activation transfers.  This module provides
a self-contained shard_map GPipe: each rank along ``axis`` owns one
contiguous stage of layer periods; microbatches stream through with
ppermute handoffs (1F1B-ish schedule: forward fill, steady state,
drain).

It is exercised by tests on a local mesh (tests/test_pipeline.py) and is
a config option for the trainer, not the default dry-run path.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh: Mesh,
                   *, axis: str = "pod"):
    """Run microbatches through pipeline stages laid out along ``axis``.

    stage_fn(params, x) -> x          (one stage's computation)
    stage_params: pytree with a leading [n_stages] axis (sharded over
        ``axis`` — each rank holds its own stage's params).
    x_micro: [n_micro, mb, ...] microbatched input (replicated).
    Returns [n_micro, mb, ...] outputs (replicated), computed as
    stage_{S-1}(... stage_0(x)).

    Schedule: n_micro + n_stages - 1 ticks.  At tick t, stage s processes
    microbatch (t - s) if 0 <= t - s < n_micro; activations ppermute to
    s+1 between ticks.  Bubble fraction = (S-1)/(n_micro + S - 1).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def local(params_stacked, xs):
        params = jax.tree_util.tree_map(lambda a: a[0], params_stacked)
        sid = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)  # activation register
        outs = jnp.zeros((n_micro,) + mb_shape, xs.dtype)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = t - sid
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 ingests a fresh microbatch from xs
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            x_in = jnp.where(sid == 0, feed, buf)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage records finished microbatches
            outs = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                lambda o: o,
                outs)
            # hand activations to the next stage
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    other = tuple(a for a in mesh.axis_names if a != axis)
    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),
    )
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P(),
                   check_vma=False)
    del other
    return fn(stage_params, x_micro)
