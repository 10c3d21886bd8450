"""Distributed SpMM / SDDMM decompositions (paper §2.4) on a TPU mesh.

The paper frames CS-3 SpMM as a distributed matmul: A streamed (not
resident), H partitioned over the PE grid => a 1.5D decomposition; H
replicated across sub-grids => 2.5D.  Across TPU chips the same taxonomy
maps onto shard_map programs:

  1.5D  A block-rows sharded over `data`; H row-sharded over `data`;
        each shard all-gathers H (comm volume N*D/p per chip per step —
        exactly the 1.5D cost), then runs the local Block-ELL kernel.
  2D    A block-rows sharded over `data`; H column-sharded over `model`;
        zero communication — each chip owns a (M/p_d, D/p_m) output tile.
        (The degenerate-communication point of the taxonomy; possible
        because every chip can hold its H column slice, unlike a CS-3 PE.)
  2.5D  multi-pod: H replicated across the `pod` axis so the 1.5D
        all-gather stays on intra-pod ICI; A sharded over (pod, data).

`allgather_matmul_overlap` is the collective-matmul trick (bidirectional
ppermute ring) used to hide the 1.5D all-gather behind the local SpMM —
compute/comm overlap, the cross-chip version of the paper's accumulator-
row buffering (§3.1.3).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.formats import BlockELL
from repro.dispatch.dispatcher import default_use_kernel
from repro.kernels.spmm.ops import spmm_blockell


def _as_blockell(a) -> BlockELL:
    """Accept a BlockELL or a ``repro.sparse.SparseMatrix``.

    The distributed decompositions shard the blocked layout; a
    SparseMatrix is unwrapped to its ``"ell"`` form (converting host-side
    if it carries only other forms).
    """
    from repro.sparse.matrix import SparseMatrix

    if isinstance(a, SparseMatrix):
        if "ell" not in a.formats:
            a = a.to("ell")
        return a.form("ell")
    return a


def _ell_specs(ell: BlockELL, row_axis) -> BlockELL:
    """PartitionSpec pytree matching a BlockELL (block-rows sharded)."""
    leaves, treedef = jax.tree_util.tree_flatten(ell)
    specs = [
        P(row_axis, None),              # indices [nbr, W]
        P(row_axis, None, None, None),  # blocks  [nbr, W, bm, bn]
        P(row_axis),                    # nblocks [nbr]
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def spmm_1p5d(ell, h, mesh: Mesh, *, row_axis: str = "data",
              use_kernel: Optional[bool] = None):
    """1.5D: A row-sharded, H row-sharded + all-gathered per step.

    ``ell``: BlockELL or ``repro.sparse.SparseMatrix``.
    """
    ell = _as_blockell(ell)
    if use_kernel is None:
        use_kernel = default_use_kernel()

    def local(ell_shard: BlockELL, h_shard):
        h_full = jax.lax.all_gather(h_shard, row_axis, axis=0, tiled=True)
        return spmm_blockell(ell_shard, h_full, use_kernel=use_kernel)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_ell_specs(ell, row_axis), P(row_axis, None)),
        out_specs=P(row_axis, None),
        check_vma=False,
    )
    return fn(ell, h)


def spmm_2d(ell, h, mesh: Mesh, *, row_axis: str = "data",
            col_axis: str = "model", use_kernel: Optional[bool] = None):
    """2D: A row-sharded over data, H column-sharded over model; no comm.

    ``ell``: BlockELL or ``repro.sparse.SparseMatrix``.
    """
    ell = _as_blockell(ell)
    if use_kernel is None:
        use_kernel = default_use_kernel()

    def local(ell_shard: BlockELL, h_shard):
        return spmm_blockell(ell_shard, h_shard, use_kernel=use_kernel)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_ell_specs(ell, row_axis), P(None, col_axis)),
        out_specs=P(row_axis, col_axis),
        check_vma=False,
    )
    return fn(ell, h)


def spmm_2p5d(ell, h, mesh: Mesh, *, pod_axis: str = "pod",
              row_axis: str = "data", use_kernel: Optional[bool] = None):
    """2.5D multi-pod: H replicated across pods; all-gather intra-pod only.

    A's block-rows are sharded over (pod, data) jointly; each pod computes
    its row stripe of Y independently — inter-pod traffic is zero inside
    the kernel (the paper's replication-trades-memory-for-comm point).
    ``ell``: BlockELL or ``repro.sparse.SparseMatrix``.
    """
    ell = _as_blockell(ell)
    if use_kernel is None:
        use_kernel = default_use_kernel()

    def local(ell_shard: BlockELL, h_shard):
        h_full = jax.lax.all_gather(h_shard, row_axis, axis=0, tiled=True)
        return spmm_blockell(ell_shard, h_full, use_kernel=use_kernel)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            _ell_specs(ell, (pod_axis, row_axis)),
            P(row_axis, None),  # H row-sharded over data, replicated on pod
        ),
        out_specs=P((pod_axis, row_axis), None),
        check_vma=False,
    )
    return fn(ell, h)


# ---------------------------------------------------------------------------
# Collective matmul: all-gather overlapped with compute via a ppermute ring
# ---------------------------------------------------------------------------


def allgather_matmul_overlap(x, w, mesh: Mesh, *, axis: str = "model"):
    """y = x @ w_full where w is row-sharded over `axis`.

    Instead of all-gather(w) then matmul (serializing comm before compute),
    runs a ring: at step t each chip multiplies the w shard it currently
    holds against the matching x column slice while ppermute-ing the shard
    to its neighbor — comm hidden behind the per-step matmul.
    x: [..., K] (replicated on `axis`); w: [K, N] sharded on rows (K).
    """
    n = mesh.shape[axis]

    def local(x_local, w_shard):
        idx = jax.lax.axis_index(axis)
        k_shard = w_shard.shape[0]
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, t):
            acc, w_cur = carry
            # shard currently held originated at chip (idx - t) mod n
            src = (idx - t) % n
            x_slice = jax.lax.dynamic_slice_in_dim(
                x_local, src * k_shard, k_shard, axis=-1)
            acc = acc + jnp.einsum("...k,kn->...n", x_slice, w_cur)
            w_next = jax.lax.ppermute(w_cur, axis, perm)
            return (acc, w_next), None

        acc0 = jnp.zeros(x_local.shape[:-1] + (w_shard.shape[1],),
                         jnp.promote_types(x_local.dtype, w_shard.dtype))
        (acc, _), _ = jax.lax.scan(step, (acc0, w_shard), jnp.arange(n))
        return acc

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis, None)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(x, w)
