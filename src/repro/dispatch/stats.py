"""Host-side matrix statistics that drive dispatch decisions.

Everything in here is plain Python numbers computed from *concrete*
(host-visible) sparse operands.  A ``MatrixStats`` is cheap to carry
around as static metadata (e.g. in a pytree aux field), so consumers
that run under ``jax.jit`` can still plan at trace time.

The central quantity is the paper's padded-stream blow-up: the ratio of
elements the Block-ELL/SELLPACK-style layout actually streams (real +
padding) to the true nonzero count.  The crossover of the paper's Fig. 9
is exactly the sparsity where that blow-up exceeds the per-element cost
advantage the streaming path has over the scalar CSR path.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.formats import (BlockCOO, BlockELL, CSR, _cdiv,
                                blockell_stream_elements,
                                sell_slot_volume, sell_tile_count)


def _structure_features(shape: Tuple[int, int], rows: np.ndarray,
                        cols: np.ndarray, row_nnz: np.ndarray
                        ) -> Dict[str, float]:
    """Row-skew and band-locality features from element coordinates.

    ``bandwidth_frac`` is the 95th percentile of the *normalized*
    diagonal distance |i/(m-1) - j/(n-1)|: ~0 for banded/diagonal
    matrices, ~0.78 for uniform-random structure (the p95 of |U - V|
    for independent uniforms).  All features are 0 for an empty matrix.
    """
    if len(rows) == 0:
        return {"row_nnz_mean": 0.0, "row_nnz_cv": 0.0, "max_row_nnz": 0,
                "bandwidth_frac": 0.0}
    m, n = shape
    mean = float(row_nnz.mean())
    cv = float(row_nnz.std() / mean) if mean > 0 else 0.0
    r_norm = rows.astype(np.float64) / max(m - 1, 1)
    c_norm = cols.astype(np.float64) / max(n - 1, 1)
    band = float(np.percentile(np.abs(r_norm - c_norm), 95))
    return {"row_nnz_mean": mean, "row_nnz_cv": cv,
            "max_row_nnz": int(row_nnz.max()), "bandwidth_frac": band}


@dataclasses.dataclass(frozen=True)
class MatrixStats:
    """Sparsity-structure summary of one sparse operand."""

    shape: Tuple[int, int]        # logical (padded) dense shape
    nnz: int                      # element-level nonzeros
    stored_elements: int          # elements the blocked layout streams
    block_m: int
    block_n: int
    n_block_rows: int
    ell_width: int                # ELL width W (0 for COO layouts)
    occupancy: float              # real blocks / stored slots (1 = no pad)
    # slots the SELL-C-σ packing would stream (real + slice padding) at
    # the default (C, σ); 0 = not measured (sell path unpriceable)
    sell_stored_elements: int = 0
    # live (block_m x block_n) tiles of that packing: the grid steps of
    # the SELL tile-walk kernels; 0 = not measured (priced at the bound
    # ``sell_tile_elements`` gives)
    sell_live_tiles: int = 0
    # -- structure features (0 = not measured, e.g. transposed stats) --
    row_nnz_mean: float = 0.0     # nnz per logical row
    row_nnz_cv: float = 0.0       # row-nnz coefficient of variation
    max_row_nnz: int = 0          # heaviest row (hub detection)
    bandwidth_frac: float = 0.0   # p95 normalized diagonal distance

    @property
    def dense_elements(self) -> int:
        return int(self.shape[0]) * int(self.shape[1])

    @property
    def density(self) -> float:
        return self.nnz / max(self.dense_elements, 1)

    @property
    def sparsity(self) -> float:
        return 1.0 - self.density

    @property
    def padded_stream_blowup(self) -> float:
        """Streamed elements per true nonzero (>= 1; inf for empty A)."""
        if self.nnz == 0:
            return float("inf")
        return self.stored_elements / self.nnz

    @property
    def ell_stream_estimate(self) -> int:
        """Elements the ELL-style streaming path must move, floored by
        row structure: every row streams at least the heaviest row's
        slot count (the global width is >= max_row_nnz / block_n slots
        per block-row), so a single hub row prices the whole layout.
        Falls back to ``stored_elements`` when row structure was never
        measured (``max_row_nnz == 0``)."""
        if self.max_row_nnz <= 0:
            return self.stored_elements
        m_pad = self.n_block_rows * max(self.block_m, 1)
        return max(self.stored_elements, m_pad * self.max_row_nnz)

    @property
    def sell_tile_elements(self) -> int:
        """Tile cells the SELL tile-walk kernels multiply: live tiles x
        block_m x block_n.  Where the tile count was not measured it is
        bounded above: each slot lies in at most one tile, and no packing
        has more tiles than block rows x block columns."""
        tiles = self.sell_live_tiles
        if tiles <= 0:
            n_block_cols = _cdiv(int(self.shape[1]), max(self.block_n, 1))
            tiles = min(self.sell_stored_elements,
                        self.n_block_rows * n_block_cols)
        return int(tiles) * self.block_m * self.block_n

    def with_capacity(self, capacity: int) -> "MatrixStats":
        """Stats restated at a mutable overlay's **slot capacity**.

        A :class:`repro.serve.runtime.DeltaGraph` patches edge deltas
        into reserved slack slots without changing any array shape, so
        the stats its served matrix carries must stay *constant* between
        repacks — otherwise every delta would change the jit aux and
        retrace every consumer.  The stable choice is to price the
        overlay at its capacity (live + slack slots): conservative for
        every per-element path, and exactly what the layout streams once
        tombstones and free slots are counted.  Inserts can open tiles,
        so the live tile count is dropped for its upper bound.  The
        planner re-prices from exact live stats at repack boundaries
        (see ``DeltaGraph.exact_stats``).
        """
        cap = int(capacity)
        if cap < self.nnz:
            raise ValueError(
                f"capacity {cap} < live nnz {self.nnz}; an overlay "
                "cannot hold fewer slots than stored elements")
        return dataclasses.replace(
            self, nnz=cap,
            stored_elements=max(self.stored_elements, cap),
            sell_stored_elements=(max(self.sell_stored_elements, cap)
                                  if self.sell_stored_elements else 0),
            sell_live_tiles=0)

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_coords(shape: Tuple[int, int], rows: np.ndarray,
                    cols: np.ndarray, block_m: int = 1, block_n: int = 1,
                    nnz: Optional[int] = None) -> "MatrixStats":
        """Blocked-layout stats from element coordinates (no blocks
        built).  This is the one shared granularity: every constructor
        below reduces to it, so stats of the same matrix agree across
        storage forms."""
        m, n = int(shape[0]), int(shape[1])
        rows = np.asarray(rows, dtype=np.int64).reshape(-1)
        cols = np.asarray(cols, dtype=np.int64).reshape(-1)
        if nnz is None:
            nnz = len(rows)
        bm, bn = int(block_m), int(block_n)
        nbr, nbc = _cdiv(m, bm), _cdiv(n, bn)
        bids = (rows // bm) * nbc + cols // bn
        ub = np.unique(bids)
        counts = np.bincount((ub // nbc).astype(np.int64), minlength=nbr)
        width = max(int(counts.max()) if len(counts) else 0, 1)
        row_nnz = np.bincount(rows, minlength=m)
        return MatrixStats(
            shape=(nbr * bm, nbc * bn),
            nnz=int(nnz),
            stored_elements=int(nbr * width * bm * bn),
            block_m=bm,
            block_n=bn,
            n_block_rows=nbr,
            ell_width=width,
            occupancy=len(ub) / max(nbr * width, 1),
            sell_stored_elements=sell_slot_volume(row_nnz),
            sell_live_tiles=sell_tile_count((m, n), rows, cols, (bm, bn)),
            **_structure_features((m, n), rows, cols, row_nnz),
        )

    @staticmethod
    def from_blockell(ell: BlockELL, nnz: Optional[int] = None
                      ) -> "MatrixStats":
        """Stats of a concrete BlockELL (host transfer of `blocks` if
        ``nnz`` is not supplied)."""
        blocks = np.asarray(ell.blocks)  # [nbr, W, bm, bn]
        if nnz is None:
            nnz = int(np.count_nonzero(blocks))
        # global element coordinates of the stored nonzeros
        br, slot, i, j = np.nonzero(blocks)
        grows = br.astype(np.int64) * ell.bm + i
        gcols = (np.asarray(ell.indices, dtype=np.int64)[br, slot] * ell.bn
                 + j)
        row_nnz = np.bincount(grows, minlength=ell.shape[0])
        nbr, w = ell.n_block_rows, ell.ell_width
        return MatrixStats(
            shape=ell.shape,
            nnz=int(nnz),
            stored_elements=int(blockell_stream_elements(ell))
            - nbr * w,  # count data words only, not the index words
            block_m=ell.bm,
            block_n=ell.bn,
            n_block_rows=nbr,
            ell_width=w,
            occupancy=ell.occupancy(),
            sell_stored_elements=sell_slot_volume(row_nnz),
            sell_live_tiles=sell_tile_count(ell.shape, grows, gcols,
                                            (ell.bm, ell.bn)),
            **_structure_features(ell.shape, grows, gcols, row_nnz),
        )

    @staticmethod
    def from_blockcoo(coo: BlockCOO, nnz: Optional[int] = None
                      ) -> "MatrixStats":
        blocks = np.asarray(coo.blocks)
        if nnz is None:
            nnz = int(np.count_nonzero(blocks))
        nnzb = coo.nnzb
        real = int((blocks.reshape(nnzb, -1) != 0).any(axis=1).sum())
        e, i, j = np.nonzero(blocks)
        grows = np.asarray(coo.rows)[e].astype(np.int64) * coo.bm + i
        gcols = np.asarray(coo.cols)[e].astype(np.int64) * coo.bn + j
        row_nnz = np.bincount(grows, minlength=coo.shape[0])
        return MatrixStats(
            shape=coo.shape,
            nnz=int(nnz),
            stored_elements=int(nnzb * coo.bm * coo.bn),
            block_m=coo.bm,
            block_n=coo.bn,
            n_block_rows=coo.shape[0] // coo.bm,
            ell_width=0,
            occupancy=real / max(nnzb, 1),
            sell_stored_elements=sell_slot_volume(row_nnz),
            sell_live_tiles=sell_tile_count(coo.shape, grows, gcols,
                                            (coo.bm, coo.bn)),
            **_structure_features(coo.shape, grows, gcols, row_nnz),
        )

    @staticmethod
    def from_csr(csr: CSR, block_m: int = 1, block_n: int = 1
                 ) -> "MatrixStats":
        """Stats of a host CSR, priced at the same blocked granularity
        as every other constructor (see :meth:`from_coords`).

        With the default 1x1 block this is element-ELL pricing: the
        streaming layout's width is the heaviest row's nnz, so
        ``stored_elements == M * max_row_nnz`` — NOT ``nnz``.  Pricing
        the ELL path at raw nnz made the same matrix auto-plan
        differently depending on which form its stats were measured
        from (csr-built stats always picked ell).
        """
        rows = np.repeat(np.arange(csr.shape[0], dtype=np.int64),
                         np.diff(csr.indptr))
        return MatrixStats.from_coords(
            csr.shape, rows, np.asarray(csr.indices, dtype=np.int64),
            block_m=block_m, block_n=block_n, nnz=csr.nnz)


def sparsity_bucket(density: float, per_decade: int = 2) -> int:
    """Discretize density into log10 buckets for autotune cache keys.

    ``per_decade`` buckets per density decade: densities within the same
    bucket share one autotune measurement.  Density 0 maps to the last
    bucket (hyper-sparse).
    """
    if density <= 0:
        return 9 * per_decade
    return int(np.clip(np.floor(-np.log10(density) * per_decade),
                       0, 9 * per_decade))
