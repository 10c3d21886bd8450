"""Sparse storage formats.

The paper's SELLPACK-like format re-buckets nonzeros of A by the consumer
PE-row's column range and pads every stream to the same length so that all
I/O channels carry uniform traffic.  The TPU-native analog implemented here
is **Block-ELL**: A is tiled into (bm x bn) blocks, each block-row keeps its
nonzero blocks left-aligned and is padded to a fixed width W with zero
blocks whose index points at an arbitrary valid block (they contribute
exactly zero to the product, the MXU analog of NULL wavelets).

``BlockCOO`` is the SDDMM-side format: the paper stores the nonzeros of a
tile of A in COO on each worker; here each nonzero *block* carries its
(block-row, block-col) coordinates.

``CSR`` mirrors the paper's host-side baseline format and is what the
streaming-footprint accounting (Fig. 8 reproduction) starts from.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = Any


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# CSR (host-side baseline; mirrors scipy.sparse.csr_matrix layout)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row; host-side (numpy) container.

    Index arrays are int32 end-to-end (matching every device-bound index
    array in the repo: BlockELL.indices/nblocks, BlockCOO.rows/cols, the
    expanded element ids); ``from_dense`` asserts nnz fits.
    """

    indptr: np.ndarray  # int32[M+1]
    indices: np.ndarray  # int32[nnz]
    values: np.ndarray  # dtype[nnz]
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    @staticmethod
    def from_dense(dense: np.ndarray) -> "CSR":
        dense = np.asarray(dense)
        m, n = dense.shape
        mask = dense != 0
        counts = mask.sum(axis=1)
        indptr64 = np.zeros(m + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr64[1:])
        nnz = int(indptr64[-1])
        if nnz >= np.iinfo(np.int32).max:
            raise ValueError(
                f"nnz={nnz} overflows the int32 index space; shard the "
                "matrix before building CSR")
        idx = np.nonzero(mask)
        return CSR(
            indptr=indptr64.astype(np.int32),
            indices=idx[1].astype(np.int32),
            values=dense[idx],
            shape=(m, n),
        )

    def to_dense(self) -> np.ndarray:
        m, n = self.shape
        out = np.zeros((m, n), dtype=self.values.dtype)
        for r in range(m):
            lo, hi = self.indptr[r], self.indptr[r + 1]
            out[r, self.indices[lo:hi]] = self.values[lo:hi]
        return out

    def nbytes(self) -> int:
        return self.indptr.nbytes + self.indices.nbytes + self.values.nbytes


# ---------------------------------------------------------------------------
# Block-ELL (SELLPACK-like, TPU-adapted)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockELL:
    """Block-ELL sparse matrix.

    indices: int32[nbr, W]   block-column ids; padded slots point at slot 0's
                             block column (any valid id) and carry zero data.
    blocks:  dtype[nbr, W, bm, bn]  block data; padded slots are all-zero.
    nblocks: int32[nbr]      true (unpadded) block count per block-row.
    shape:   (M, N) logical dense shape (multiples of bm / bn after padding).
    """

    indices: Array
    blocks: Array
    nblocks: Array
    shape: Tuple[int, int]

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        return (self.indices, self.blocks, self.nblocks), self.shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        indices, blocks, nblocks = children
        return cls(indices=indices, blocks=blocks, nblocks=nblocks, shape=aux)

    # -- derived metadata ---------------------------------------------------
    @property
    def bm(self) -> int:
        return self.blocks.shape[2]

    @property
    def bn(self) -> int:
        return self.blocks.shape[3]

    @property
    def n_block_rows(self) -> int:
        return self.indices.shape[0]

    @property
    def ell_width(self) -> int:
        return self.indices.shape[1]

    @property
    def dtype(self):
        return self.blocks.dtype

    def nbytes(self) -> int:
        return sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in (self.indices, self.blocks, self.nblocks))

    # -- conversions ---------------------------------------------------------
    @staticmethod
    def from_dense(
        dense: np.ndarray,
        bm: int,
        bn: int,
        ell_width: int | None = None,
    ) -> "BlockELL":
        """Tile ``dense`` into (bm, bn) blocks and keep nonzero blocks.

        The dense input is zero-padded up to multiples of (bm, bn).  If
        ``ell_width`` is given, block-rows with more nonzero blocks raise.
        """
        dense = np.asarray(dense)
        m, n = dense.shape
        mp, np_ = _cdiv(m, bm) * bm, _cdiv(n, bn) * bn
        if (mp, np_) != (m, n):
            pad = np.zeros((mp, np_), dtype=dense.dtype)
            pad[:m, :n] = dense
            dense = pad
        nbr, nbc = mp // bm, np_ // bn
        tiles = dense.reshape(nbr, bm, nbc, bn).transpose(0, 2, 1, 3)
        nz = tiles.reshape(nbr, nbc, -1).any(axis=-1)  # bool[nbr, nbc]
        counts = nz.sum(axis=1).astype(np.int32)
        width = int(counts.max()) if ell_width is None else int(ell_width)
        width = max(width, 1)
        if (counts > width).any():
            raise ValueError(
                f"ell_width={width} < max nonzero blocks per row "
                f"({int(counts.max())})")
        indices = np.zeros((nbr, width), dtype=np.int32)
        blocks = np.zeros((nbr, width, bm, bn), dtype=dense.dtype)
        for i in range(nbr):
            cols = np.nonzero(nz[i])[0]
            indices[i, : len(cols)] = cols
            blocks[i, : len(cols)] = tiles[i, cols]
            # padded slots: index 0 (or first real col), zero data
            if len(cols) == 0:
                indices[i, :] = 0
            else:
                indices[i, len(cols):] = cols[0]
        return BlockELL(
            indices=jnp.asarray(indices),
            blocks=jnp.asarray(blocks),
            nblocks=jnp.asarray(counts),
            shape=(mp, np_),
        )

    def to_dense(self) -> np.ndarray:
        """Inverse of from_dense (padded shape)."""
        indices = np.asarray(self.indices)
        blocks = np.asarray(self.blocks)
        nblocks = np.asarray(self.nblocks)
        nbr, w = indices.shape
        bm, bn = self.bm, self.bn
        nbc = self.shape[1] // bn
        out = np.zeros((nbr, nbc, bm, bn), dtype=blocks.dtype)
        for i in range(nbr):
            for s in range(int(nblocks[i])):
                out[i, indices[i, s]] += blocks[i, s]
        return out.transpose(0, 2, 1, 3).reshape(self.shape)

    def occupancy(self) -> float:
        """Fraction of ELL slots that hold real blocks (1.0 = no padding)."""
        total = self.n_block_rows * self.ell_width
        return float(np.asarray(self.nblocks).sum()) / max(total, 1)


# ---------------------------------------------------------------------------
# Block-COO (SDDMM-side format)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockCOO:
    """Coordinate list of nonzero (bm x bn) blocks.

    rows/cols: int32[nnzb] block coordinates (padded entries repeat slot 0 and
               carry an all-zero mask so they contribute nothing).
    blocks:    dtype[nnzb, bm, bn] block data (for SDDMM this is the sampling
               mask / values of A).
    """

    rows: Array
    cols: Array
    blocks: Array
    shape: Tuple[int, int]

    def tree_flatten(self):
        return (self.rows, self.cols, self.blocks), self.shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        rows, cols, blocks = children
        return cls(rows=rows, cols=cols, blocks=blocks, shape=aux)

    @property
    def bm(self) -> int:
        return self.blocks.shape[1]

    @property
    def bn(self) -> int:
        return self.blocks.shape[2]

    @property
    def nnzb(self) -> int:
        return self.rows.shape[0]

    def nbytes(self) -> int:
        return sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in (self.rows, self.cols, self.blocks))

    @staticmethod
    def from_dense(
        dense: np.ndarray, bm: int, bn: int, pad_to: int | None = None
    ) -> "BlockCOO":
        dense = np.asarray(dense)
        m, n = dense.shape
        mp, np_ = _cdiv(m, bm) * bm, _cdiv(n, bn) * bn
        if (mp, np_) != (m, n):
            pad = np.zeros((mp, np_), dtype=dense.dtype)
            pad[:m, :n] = dense
            dense = pad
        nbr, nbc = mp // bm, np_ // bn
        tiles = dense.reshape(nbr, bm, nbc, bn).transpose(0, 2, 1, 3)
        nz = tiles.reshape(nbr, nbc, -1).any(axis=-1)
        ridx, cidx = np.nonzero(nz)
        nnzb = len(ridx)
        if nnzb == 0:
            ridx, cidx = np.zeros(1, np.int64), np.zeros(1, np.int64)
            blocks = np.zeros((1, bm, bn), dtype=dense.dtype)
            nnzb = 1
        else:
            blocks = tiles[ridx, cidx]
        if pad_to is not None and pad_to > nnzb:
            padn = pad_to - nnzb
            ridx = np.concatenate([ridx, np.full(padn, ridx[0])])
            cidx = np.concatenate([cidx, np.full(padn, cidx[0])])
            blocks = np.concatenate(
                [blocks, np.zeros((padn, bm, bn), dtype=blocks.dtype)])
        return BlockCOO(
            rows=jnp.asarray(ridx, jnp.int32),
            cols=jnp.asarray(cidx, jnp.int32),
            blocks=jnp.asarray(blocks),
            shape=(mp, np_),
        )

    def to_dense(self) -> np.ndarray:
        rows = np.asarray(self.rows)
        cols = np.asarray(self.cols)
        blocks = np.asarray(self.blocks)
        bm, bn = self.bm, self.bn
        nbr, nbc = self.shape[0] // bm, self.shape[1] // bn
        out = np.zeros((nbr, nbc, bm, bn), dtype=blocks.dtype)
        # Padded duplicates carry zero blocks; += keeps them harmless.
        np.add.at(out, (rows, cols), blocks)
        return out.transpose(0, 2, 1, 3).reshape(self.shape)


# ---------------------------------------------------------------------------
# SELL-C-σ (tile-pruned, row-sorted packing for the hyper-sparse regime)
# ---------------------------------------------------------------------------

# Defaults shared by the packer and the stats layer (they must agree so
# the cost model prices exactly the layout the packer would build).
SELL_C = 8          # slice height (rows per width-adaptive slice)
SELL_SIGMA = 0      # sort-window size in rows; 0 = sort the whole matrix

# Geometric width ladder (~1.5x growth): slice widths round *up* onto it,
# so padding is bounded (<= 50 %, typically ~10 %) while the number of
# distinct widths — and hence jnp reference buckets — stays O(log nnz).
def _width_ladder(upto: int) -> np.ndarray:
    vals = [1]
    while vals[-1] < upto:
        q = vals[-1]
        vals.append(q + 1 if q < 2 else q * 3 // 2)
    return np.array(vals, dtype=np.int64)


def _quantize_width(w: int) -> int:
    if w <= 0:
        return 0
    return int(_width_ladder(w)[-1])


def _sell_row_order(row_nnz: np.ndarray, c: int, sigma: int,
                    width_slack: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(row order, quantized slice widths) of the SELL-C-σ packing.

    Rows are sorted by nnz (descending, stable) within ``sigma``-row
    windows, grouped into slices of ``c`` rows, and each slice's width is
    the quantized max nnz of its rows.  Pure function of the per-row
    nonzero counts — `MatrixStats` uses it to price the layout without
    packing anything, so it runs on every stats construction and stays
    vectorized.

    ``width_slack`` reserves that many extra (zero) slots per row of
    every non-empty slice *before* quantization — the mutable-overlay
    headroom ``DeltaGraph`` patches edge inserts into.  The default 0
    reproduces the historical packing exactly (and is what the stats
    layer prices).
    """
    m = len(row_nnz)
    mp = _cdiv(max(m, 1), c) * c
    padded = np.zeros(mp, dtype=np.int64)
    padded[:m] = row_nnz
    sigma = sigma if sigma and sigma > 0 else mp
    order = np.concatenate([
        w0 + np.argsort(-padded[w0:w0 + sigma], kind="stable")
        for w0 in range(0, mp, sigma)
    ]) if mp else np.zeros(0, np.int64)
    slice_max = padded[order].reshape(-1, c).max(axis=1) if mp \
        else np.zeros(0, np.int64)
    target = np.where(slice_max > 0, slice_max + int(width_slack), 0)
    ladder = _width_ladder(int(target.max()) if len(target) else 1)
    widths = np.where(
        target > 0,
        ladder[np.searchsorted(ladder, target, side="left")
               .clip(max=len(ladder) - 1)],
        0)
    return order, widths


def sell_slot_volume(row_nnz: np.ndarray, c: int = SELL_C,
                     sigma: int = SELL_SIGMA) -> int:
    """Padded slot count of the SELL-C-σ packing (empty slices pruned).

    This is the `stored_elements` analog for the sell path: the exact
    number of (col, value) slots the packed layout streams.
    """
    _, widths = _sell_row_order(np.asarray(row_nnz), c, sigma)
    return int(widths.sum()) * c


def sell_tile_count(shape: Tuple[int, int], rows: np.ndarray,
                    cols: np.ndarray, block: Tuple[int, int],
                    c: int = SELL_C, sigma: int = SELL_SIGMA) -> int:
    """Live (bm x bn) tiles of the SELL-C-σ packing of these coordinates:
    the grid steps the tile-walk kernels take (``SellCS.n_tiles`` of
    ``SellCS.from_dense`` with the same C, σ and block).

    A tile is a distinct (packed row // bm, col // bn) pair.  The packed
    row order is ``from_dense``'s: non-empty slices grouped by ascending
    width, slice order kept within a width.
    """
    rows = np.asarray(rows, dtype=np.int64).reshape(-1)
    cols = np.asarray(cols, dtype=np.int64).reshape(-1)
    if len(rows) == 0:
        return 0
    row_nnz = np.bincount(rows, minlength=int(shape[0]))
    order, widths = _sell_row_order(row_nnz, c, sigma)
    live = np.nonzero(widths > 0)[0]
    slice_pos = np.zeros(len(widths), np.int64)
    slice_pos[live[np.argsort(widths[live], kind="stable")]] = \
        np.arange(len(live))
    lane = np.arange(len(order))
    packed = np.empty(len(order), np.int64)
    packed[order] = slice_pos[lane // c] * c + lane % c
    bm, bn = block
    keys = (packed[rows] // bm) * _cdiv(int(shape[1]), bn) + cols // bn
    return len(np.unique(keys))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SellCS:
    """SELL-C-σ sparse matrix with a tile-pruned block companion view.

    Two synchronized views of the same nonzeros:

    **Slot view** (element-granular, the differentiable storage): rows
    sorted by nnz within σ-windows, grouped into slices of C rows, each
    slice padded to its own quantized width — never to a global max, so
    the hyper-sparsity padding cliff of Block-ELL cannot happen.  Slices
    whose width is 0 (all-empty rows) are dropped entirely.  Same-width
    slices are stored contiguously (``buckets``), so the jnp reference
    runs one scatter-free batched contraction per width bucket.

    * ``slot_cols``/``slot_rows``: int32[n_slots] original coordinates
      per slot (padding slots repeat the row's first column and carry
      zero values).
    * ``slot_vals``: dtype[n_slots] — THE values leaf; gradients flow
      here, padding slots are structural zeros.
    * ``out_gather``: int32[M] original row -> packed row (``n_packed``
      for rows in pruned slices; the consumer appends a zero row).

    **Tile view** (block-granular, what the Pallas kernels iterate):
    the packed row axis is tiled into (bm x bn) blocks and only live
    (non-empty) tiles are kept, ordered block-row-major.  Block-rows
    with no live tile are never launched — the explicit non-empty-tile
    map of the kernel grid.

    * ``perm``: int32[n_live*bm] live packed row -> original row (M for
      padding rows) — the row gather SDDMM applies to B.
    * ``tile_rows``/``tile_cols``: int32[T] live-tile coordinates
      (compacted block-row, original block-column).
    * ``tile_slot_map``: int32[T, bm, bn] tile cell -> slot id
      (``n_slots`` for dead cells) — the tile structure the SDDMM mask,
      ``DeltaGraph`` and block-diagonal batches read.
    * ``slot_tile_pos``: int32[n_slots] slot -> flat tile-cell position
      (``T*bm*bn`` for padding and deleted slots) — where tile data is
      scattered from ``slot_vals`` (so the values live exactly once),
      and how SDDMM tile output folds back into slot order.
    * ``tile_out_gather``: int32[M] original row -> row of the compact
      kernel output (``n_live*bm`` for pruned rows).

    Static aux: logical ``shape``, slice height ``c``, sort window
    ``sigma`` (0 = whole matrix), ``buckets`` — a tuple of
    ``(row_offset, n_rows, width)`` per width bucket in storage order —
    the tile ``block`` and the live block-row count.
    """

    slot_cols: Array
    slot_rows: Array
    slot_vals: Array
    out_gather: Array
    perm: Array
    tile_rows: Array
    tile_cols: Array
    tile_slot_map: Array
    slot_tile_pos: Array
    tile_out_gather: Array
    shape: Tuple[int, int]
    c: int
    sigma: int
    buckets: Tuple[Tuple[int, int, int], ...]
    block: Tuple[int, int]
    n_live_block_rows: int

    _CHILDREN = ("slot_cols", "slot_rows", "slot_vals", "out_gather",
                 "perm", "tile_rows", "tile_cols", "tile_slot_map",
                 "slot_tile_pos", "tile_out_gather")

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in self._CHILDREN)
        aux = (self.shape, self.c, self.sigma, self.buckets, self.block,
               self.n_live_block_rows)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, c, sigma, buckets, block, n_live = aux
        return cls(*children, shape=shape, c=c, sigma=sigma,
                   buckets=buckets, block=block, n_live_block_rows=n_live)

    # -- derived metadata ---------------------------------------------------
    @property
    def bm(self) -> int:
        return self.block[0]

    @property
    def bn(self) -> int:
        return self.block[1]

    @property
    def n_slots(self) -> int:
        return sum(r * w for _, r, w in self.buckets)

    @property
    def n_packed_rows(self) -> int:
        return sum(r for _, r, _ in self.buckets)

    @property
    def n_tiles(self) -> int:
        return int(self.tile_rows.shape[0])

    @property
    def dtype(self):
        return self.slot_vals.dtype

    def nbytes(self) -> int:
        return sum(int(np.prod(np.shape(getattr(self, f))))
                   * np.dtype(getattr(self, f).dtype).itemsize
                   for f in self._CHILDREN)

    def stream_elements(self) -> int:
        """Slots the packed layout streams (the sell `stored_elements`)."""
        return self.n_slots

    # -- conversions ---------------------------------------------------------
    @staticmethod
    def from_dense(dense: np.ndarray, *, c: int = SELL_C,
                   sigma: int = SELL_SIGMA,
                   block: Tuple[int, int] = (64, 64),
                   width_slack: int = 0) -> "SellCS":
        """Pack a concrete dense matrix into SELL-C-σ.

        ``block`` sets the (bm, bn) tile geometry of the kernel view; it
        is independent of the slice height ``c``.  ``width_slack``
        reserves extra zero slots per row of every non-empty slice (the
        in-place-patchable headroom a ``DeltaGraph`` overlay consumes);
        0 keeps the historical packing.
        """
        dense = np.asarray(dense)
        m, n = dense.shape
        bm, bn = block
        row_nnz = (dense != 0).sum(axis=1)
        order, widths = _sell_row_order(row_nnz, c, sigma, width_slack)
        mp = len(order)

        # group equal-width slices into buckets (ascending width); the
        # packed row order is bucket-major, slice-order-preserving
        by_width: Dict[int, list] = {}
        for s, w in enumerate(widths):
            if w > 0:
                by_width.setdefault(int(w), []).append(s)
        buckets = []
        packed_rows = []  # original (padded) row id per packed row
        for w in sorted(by_width):
            slices = by_width[w]
            buckets.append((len(packed_rows), len(slices) * c, w))
            for s in slices:
                packed_rows.extend(order[s * c:(s + 1) * c])
        n_packed = len(packed_rows)

        # slot view (one nonzero scan per row, reused by the tile view)
        n_slots = sum(r * w for _, r, w in buckets)
        slot_cols = np.zeros(n_slots, np.int32)
        slot_rows = np.zeros(n_slots, np.int32)
        slot_vals = np.zeros(n_slots, dense.dtype)
        out_gather = np.full(m, n_packed, np.int32)
        slot_start = {}  # packed row -> offset of its first slot
        row_cols = {}    # packed row -> its nonzero column indices
        off = 0
        for row_off, n_rows, w in buckets:
            for i in range(n_rows):
                r = packed_rows[row_off + i]
                lo = off + i * w
                slot_start[row_off + i] = lo
                if r < m:
                    cc = np.nonzero(dense[r])[0]
                    row_cols[row_off + i] = cc
                    k = len(cc)
                    slot_cols[lo:lo + w] = cc[0] if k else 0
                    slot_cols[lo:lo + k] = cc
                    slot_rows[lo:lo + w] = r
                    slot_vals[lo:lo + k] = dense[r, cc]
                    out_gather[r] = row_off + i
                # rows >= m are slice padding: zero slots at (0, 0)
            off += n_rows * w

        # tile view: block the packed row axis, keep live tiles only
        tiles: Dict[Tuple[int, int], np.ndarray] = {}
        for p, cc in row_cols.items():
            lo = slot_start[p]
            for k, col in enumerate(cc):
                key = (p // bm, col // bn)
                cell = tiles.get(key)
                if cell is None:
                    cell = np.full((bm, bn), n_slots, np.int32)
                    tiles[key] = cell
                cell[p % bm, col % bn] = lo + k

        live_brs = sorted({br for br, _ in tiles})
        br_compact = {br: i for i, br in enumerate(live_brs)}
        n_live = len(live_brs)
        keys = sorted(tiles)  # block-row-major, then block-column
        t_count = len(keys)
        tile_rows = np.zeros(t_count, np.int32)
        tile_cols = np.zeros(t_count, np.int32)
        tile_slot_map = np.full((t_count, bm, bn), n_slots, np.int32)
        for t, (br, bc) in enumerate(keys):
            tile_rows[t] = br_compact[br]
            tile_cols[t] = bc
            tile_slot_map[t] = tiles[(br, bc)]
        slot_tile_pos = np.full(n_slots, t_count * bm * bn, np.int32)
        flat = tile_slot_map.reshape(-1)
        live = flat < n_slots
        slot_tile_pos[flat[live]] = np.nonzero(live)[0].astype(np.int32)

        # perm: live packed row -> original row (M for padding rows)
        perm = np.full(n_live * bm, m, np.int32)
        tile_out_gather = np.full(m, n_live * bm, np.int32)
        for i, br in enumerate(live_brs):
            for j in range(bm):
                p = br * bm + j
                if p < n_packed and packed_rows[p] < m:
                    perm[i * bm + j] = packed_rows[p]
                    tile_out_gather[packed_rows[p]] = i * bm + j

        return SellCS(
            slot_cols=jnp.asarray(slot_cols),
            slot_rows=jnp.asarray(slot_rows),
            slot_vals=jnp.asarray(slot_vals),
            out_gather=jnp.asarray(out_gather),
            perm=jnp.asarray(perm),
            tile_rows=jnp.asarray(tile_rows),
            tile_cols=jnp.asarray(tile_cols),
            tile_slot_map=jnp.asarray(tile_slot_map),
            slot_tile_pos=jnp.asarray(slot_tile_pos),
            tile_out_gather=jnp.asarray(tile_out_gather),
            shape=(m, n),
            c=c,
            sigma=sigma,
            buckets=tuple(buckets),
            block=(bm, bn),
            n_live_block_rows=n_live,
        )

    def to_dense(self) -> np.ndarray:
        """Host densification (scatter the slots; padding adds zeros)."""
        m, n = self.shape
        out = np.zeros((m, n), np.asarray(self.slot_vals).dtype)
        rows = np.asarray(self.slot_rows)
        cols = np.asarray(self.slot_cols)
        vals = np.asarray(self.slot_vals)
        np.add.at(out, (rows, cols), vals)
        return out

    def occupancy(self) -> float:
        """Real nonzeros per stored slot (1.0 = zero padding)."""
        nnz = int(np.count_nonzero(np.asarray(self.slot_vals)))
        return nnz / max(self.n_slots, 1)


# ---------------------------------------------------------------------------
# Paper-faithful SELLPACK-like stream accounting (Fig. 8 reproduction)
# ---------------------------------------------------------------------------


def sellpack_stream_elements(
    csr: CSR, max_y_chunk: int, max_v_per_pe: int
) -> int:
    """Total (index,value)-pair count streamed in the paper's SELLPACK-like
    format.

    The host slices A into chunks of ``max_y_chunk`` rows.  Within a chunk,
    the nonzeros of each row are re-bucketed by worker-row column range
    (``max_v_per_pe`` wide).  Every bucket's stream carries one END_ROW
    marker per *run* of row terminations (run-length encoded: consecutive
    empty rows collapse into a single END_ROW pair), and all streams in a
    chunk are padded with NULLs to the chunk's longest stream.
    """
    m, n = csr.shape
    n_buckets = _cdiv(n, max_v_per_pe)
    total = 0
    for c0 in range(0, m, max_y_chunk):
        c1 = min(c0 + max_y_chunk, m)
        # per-bucket stream length for this chunk
        lengths = np.zeros(n_buckets, dtype=np.int64)
        # nonzero counts: bucket each row's column indices
        prev_emitted_end = np.zeros(n_buckets, dtype=bool)
        for r in range(c0, c1):
            lo, hi = csr.indptr[r], csr.indptr[r + 1]
            cols = csr.indices[lo:hi]
            counts = np.bincount(cols // max_v_per_pe, minlength=n_buckets)
            lengths += counts
            # END_ROW run-length coding: a bucket that receives nonzeros for
            # this row must emit an END_ROW afterwards; a bucket receiving
            # nothing extends the previous END_ROW run (no new element).
            has_data = counts > 0
            new_end = has_data | ~prev_emitted_end
            lengths += new_end.astype(np.int64)
            prev_emitted_end = np.ones(n_buckets, dtype=bool)
        total += int(lengths.max()) * n_buckets  # NULL-padded to equal length
    return total


def blockell_stream_elements(ell: BlockELL) -> int:
    """Elements (index or value words) resident in the Block-ELL layout —
    the TPU analog of the paper's streamed-element count."""
    return int(np.prod(ell.blocks.shape)) + int(np.prod(ell.indices.shape))
