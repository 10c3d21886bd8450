"""Tile-pruned SELL-C-σ Pallas kernels vs jnp oracles (interpret mode).

The SpMM kernel's flush-on-row-change logic (width-adaptive: each
block-row owns a different number of grid steps) is the part the global
fixed-width Block-ELL kernel never exercises, so the parity sweep leans
on skewed and pruned structures.  Larger parity cases are slow-marked
for the scheduled kernel-parity CI job (``--runslow``).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.batch.block_diag import BatchedSparseMatrix
from repro.core.formats import SellCS
from repro.kernels.sddmm.sell import sample_sell_blocked, tile_slots
from repro.kernels.spmm.sell import (sell_tile_blocks, spmm_sell_blocked,
                                     spmm_sell_kernel, spmm_sell_tiles_ref)
from repro.serve.runtime.delta import DeltaGraph
from repro.sparse.matrix import SparseMatrix
from repro.sparse.paths import spmm_sell_ref


def _rand_sparse(rng, m, n, density):
    mask = rng.random((m, n)) < density
    return np.where(mask, rng.normal(size=(m, n)), 0.0).astype(np.float32)


def _pad_h(sell, h):
    n_pad = -(-sell.shape[1] // sell.bn) * sell.bn
    out = np.zeros((n_pad, h.shape[1]), h.dtype)
    out[: h.shape[0]] = h
    return jnp.asarray(out)


@pytest.mark.parametrize("m,n,block,c", [
    (128, 128, (16, 16), 8),
    (100, 70, (4, 4), 8),      # ragged vs the tile grid
    (256, 128, (8, 16), 4),    # rectangular tiles
])
@pytest.mark.parametrize("density", [0.005, 0.05, 0.3])
def test_spmm_sell_kernel_matches_oracles(rng, m, n, block, c, density):
    dense = _rand_sparse(rng, m, n, density)
    sell = SellCS.from_dense(dense, c=c, block=block)
    d = 32
    h = rng.normal(size=(n, d)).astype(np.float32)
    out = np.asarray(spmm_sell_blocked(sell, jnp.asarray(h),
                                       interpret=True))
    np.testing.assert_allclose(out, dense @ h, rtol=5e-4, atol=5e-4)
    # kernel == tile-granular jnp oracle on the compact output
    if sell.n_live_block_rows:
        hh = _pad_h(sell, h)
        compact = spmm_sell_kernel(
            sell.tile_rows, sell.tile_cols, sell_tile_blocks(sell), hh,
            n_live_block_rows=sell.n_live_block_rows, bd=d,
            interpret=True)
        ref = spmm_sell_tiles_ref(
            sell.tile_rows, sell.tile_cols, sell_tile_blocks(sell), hh,
            n_live_block_rows=sell.n_live_block_rows)
        np.testing.assert_allclose(np.asarray(compact), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
    # kernel route == bucketed reference route
    ref2 = np.asarray(spmm_sell_ref(sell, jnp.asarray(h)))
    np.testing.assert_allclose(out, ref2, rtol=5e-4, atol=5e-4)


def test_spmm_sell_skewed_widths(rng):
    """A few hot rows + many near-empty rows: block-rows own wildly
    different live-tile counts, stressing the flush logic."""
    dense = np.zeros((256, 256), np.float32)
    dense[:4] = _rand_sparse(rng, 4, 256, 0.6)       # hot rows
    dense[100:140] = _rand_sparse(rng, 40, 256, 0.01)
    dense[255, 255] = 2.0                            # lone corner element
    sell = SellCS.from_dense(dense, c=8, block=(8, 8))
    h = rng.normal(size=(256, 64)).astype(np.float32)
    out = np.asarray(spmm_sell_blocked(sell, jnp.asarray(h),
                                       interpret=True))
    np.testing.assert_allclose(out, dense @ h, rtol=5e-4, atol=5e-4)


def test_spmm_sell_empty_rows_never_launch(rng):
    """Pruned (all-zero) rows produce exact zeros via the epilogue
    gather — they are not kernel output."""
    dense = np.zeros((128, 128), np.float32)
    dense[:8] = _rand_sparse(rng, 8, 128, 0.2)
    sell = SellCS.from_dense(dense, c=8, block=(16, 16))
    assert sell.n_live_block_rows == 1  # 8 live rows -> one block-row
    h = rng.normal(size=(128, 32)).astype(np.float32)
    out = np.asarray(spmm_sell_blocked(sell, jnp.asarray(h),
                                       interpret=True))
    assert np.all(out[8:] == 0.0)
    np.testing.assert_allclose(out, dense @ h, rtol=5e-4, atol=5e-4)


def test_spmm_sell_empty_matrix():
    sell = SellCS.from_dense(np.zeros((64, 64), np.float32))
    out = spmm_sell_blocked(sell, jnp.ones((64, 8), jnp.float32),
                            interpret=True)
    assert out.shape == (64, 8)
    assert np.all(np.asarray(out) == 0.0)


def _cell_gather(sell):
    """The tile view's values read cell by cell through
    ``tile_slot_map``: dead cells read an appended zero slot."""
    vals_ext = jnp.concatenate(
        [sell.slot_vals, jnp.zeros((1,), sell.slot_vals.dtype)])
    return vals_ext[sell.tile_slot_map]


def _skewed(rng):
    dense = np.zeros((256, 256), np.float32)
    dense[:4] = _rand_sparse(rng, 4, 256, 0.6)
    dense[100:140] = _rand_sparse(rng, 40, 256, 0.01)
    dense[255, 255] = 2.0
    return dense


def _block_diag_sell(rng):
    mats = [SparseMatrix.from_dense(_rand_sparse(rng, m, m, 0.05),
                                    formats=("sell",), block=(8, 8))
            for m in (40, 64, 24)]
    return BatchedSparseMatrix.from_matrices(
        mats, formats=("sell",)).matrix.form("sell")


def _delta_sell(rng):
    """A SELL overlay after in-place inserts and deletes: inserted slots
    sit in live tiles, deleted slots point at the dead cell."""
    dense = _rand_sparse(rng, 64, 64, 0.1)
    dg = DeltaGraph(dense, form="sell", c=16, block=(8, 8))
    rows, cols = np.nonzero(dense)
    inserted = 0
    for r, c in zip(rows, cols):
        mate = (c // 8) * 8 + (c + 1) % 8   # same tile, fresh cell
        if dense[r, mate] == 0 and inserted < 6:
            dg.insert(int(r), int(mate), 1.5)
            dense[r, mate] = 1.5
            inserted += 1
    for r, c in list(zip(rows, cols))[::7]:
        dg.delete(int(r), int(c))
    assert inserted == 6 and dg.repacks == 0
    return dg.matrix.form("sell")


SELL_CASES = {
    "ragged": lambda rng: SellCS.from_dense(
        _rand_sparse(rng, 100, 70, 0.05), c=8, block=(4, 4)),
    "ragged-slack": lambda rng: SellCS.from_dense(
        _rand_sparse(rng, 100, 70, 0.05), c=8, block=(4, 4),
        width_slack=2),
    "rectangular": lambda rng: SellCS.from_dense(
        _rand_sparse(rng, 256, 128, 0.05), c=4, block=(8, 16)),
    "rectangular-slack": lambda rng: SellCS.from_dense(
        _rand_sparse(rng, 256, 128, 0.05), c=4, block=(8, 16),
        width_slack=2),
    "skewed": lambda rng: SellCS.from_dense(_skewed(rng), c=8,
                                            block=(8, 8)),
    "skewed-slack": lambda rng: SellCS.from_dense(
        _skewed(rng), c=8, block=(8, 8), width_slack=2),
    "block-diag": _block_diag_sell,
    "delta": _delta_sell,
    "empty": lambda rng: SellCS.from_dense(np.zeros((64, 64), np.float32)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(SELL_CASES))
def test_sell_tile_blocks_equals_cell_gather(rng, case, dtype):
    sell = SELL_CASES[case](rng)
    sell = dataclasses.replace(sell, slot_vals=sell.slot_vals.astype(dtype))
    got = np.asarray(jax.jit(sell_tile_blocks)(sell))
    want = np.asarray(jax.jit(_cell_gather)(sell))
    assert got.dtype == want.dtype and got.shape == want.shape
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))


def _flat_slot_read(sell, tiles):
    """Each slot's cell read from the flattened tile output with an
    appended zero cell for the padding slots."""
    flat = jnp.concatenate([tiles.reshape(-1), jnp.zeros((1,), tiles.dtype)])
    return flat[sell.slot_tile_pos]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(SELL_CASES))
def test_tile_slots_equals_flat_slot_read(rng, case, dtype):
    sell = SELL_CASES[case](rng)
    tiles = jnp.asarray(rng.normal(size=sell.tile_slot_map.shape), dtype)
    got = np.asarray(jax.jit(tile_slots)(sell, tiles))
    want = np.asarray(jax.jit(_flat_slot_read)(sell, tiles))
    assert got.dtype == want.dtype and got.shape == (sell.n_slots,)
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))


def _large_ops(fn, args, n_elements):
    """(opcode, shape) of each instruction in ``fn``'s compiled HLO,
    parameters aside, whose result has at least ``n_elements``."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = []
    for shape, op in re.findall(r"= \w+\[([\d,]*)\]\S* (\w[\w-]*)\(",
                                text):
        size = int(np.prod([int(d) for d in shape.split(",") if d]))
        if op != "parameter" and size >= n_elements:
            found.append((op, shape))
    return found


def test_tile_view_reads_and_writes_no_cell_per_tile_cell(rng):
    """The tile view's values cost one write per slot, and the slots'
    read of the SDDMM tile output one read per slot: no gather over
    every cell of every tile, and no flattened copy of the tile output
    (the detector finds both in the cell-wise forms)."""
    sell = SellCS.from_dense(_rand_sparse(rng, 128, 128, 0.02), c=8,
                             block=(16, 16), width_slack=2)
    n_cells = int(np.prod(sell.tile_slot_map.shape))
    assert sell.n_slots < n_cells
    tiles = jnp.asarray(rng.normal(size=sell.tile_slot_map.shape),
                        jnp.float32)

    def gathers(fn, *args):
        return [op for op, _ in _large_ops(fn, args, n_cells)
                if op == "gather"]

    assert gathers(_cell_gather, sell)
    assert gathers(sell_tile_blocks, sell) == []
    assert _large_ops(_flat_slot_read, (sell, tiles), n_cells)
    assert _large_ops(tile_slots, (sell, tiles), n_cells) == []


def test_sell_tile_blocks_gradient_reads_each_slot_cell(rng):
    sell = SellCS.from_dense(_rand_sparse(rng, 100, 70, 0.05), c=8,
                             block=(4, 4), width_slack=2)
    w = rng.normal(size=sell.tile_slot_map.shape).astype(np.float32)

    def loss(vals):
        return jnp.sum(sell_tile_blocks(
            dataclasses.replace(sell, slot_vals=vals)) * w)

    grad = np.asarray(jax.jit(jax.grad(loss))(sell.slot_vals))
    pos = np.asarray(sell.slot_tile_pos)
    live = pos < w.size
    assert live.any() and not live.all()   # padding slots are present
    want = np.where(live, w.reshape(-1)[np.minimum(pos, w.size - 1)], 0.0)
    np.testing.assert_array_equal(grad, want)


@pytest.mark.parametrize("density", [0.01, 0.2])
def test_sddmm_sell_kernel_matches_dense_sample(rng, density):
    m, n, k = 128, 96, 64
    dense = _rand_sparse(rng, m, n, density)
    sell = SellCS.from_dense(dense, c=8, block=(16, 16))
    b = rng.normal(size=(m, k)).astype(np.float32)
    c = rng.normal(size=(k, n)).astype(np.float32)
    dots = np.asarray(sample_sell_blocked(
        sell, jnp.asarray(b), jnp.asarray(c), interpret=True))
    full = b @ c
    sr = np.asarray(sell.slot_rows)
    sc = np.asarray(sell.slot_cols)
    real = np.asarray(sell.slot_vals) != 0
    np.testing.assert_allclose(dots[real], full[sr[real], sc[real]],
                               rtol=1e-3, atol=1e-3)


@pytest.mark.slow
@pytest.mark.parametrize("m,n,d,block", [
    (512, 512, 256, (64, 128)),
    (384, 768, 128, (128, 128)),
])
@pytest.mark.parametrize("density", [0.002, 0.02, 0.2])
def test_spmm_sell_kernel_parity_large(rng, m, n, d, block, density):
    """Slow kernel-parity sweep (scheduled CI job): MXU-shaped tiles."""
    dense = _rand_sparse(rng, m, n, density)
    sell = SellCS.from_dense(dense, c=16, block=block)
    h = rng.normal(size=(n, d)).astype(np.float32)
    out = np.asarray(spmm_sell_blocked(sell, jnp.asarray(h),
                                       interpret=True))
    np.testing.assert_allclose(out, dense @ h, rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_sddmm_sell_kernel_parity_large(rng):
    m, n, k = 512, 512, 256
    dense = _rand_sparse(rng, m, n, 0.01)
    sell = SellCS.from_dense(dense, c=16, block=(64, 64))
    b = rng.normal(size=(m, k)).astype(np.float32)
    c = rng.normal(size=(k, n)).astype(np.float32)
    dots = np.asarray(sample_sell_blocked(
        sell, jnp.asarray(b), jnp.asarray(c), interpret=True))
    full = b @ c
    sr = np.asarray(sell.slot_rows)
    sc = np.asarray(sell.slot_cols)
    real = np.asarray(sell.slot_vals) != 0
    np.testing.assert_allclose(dots[real], full[sr[real], sc[real]],
                               rtol=1e-2, atol=1e-2)
