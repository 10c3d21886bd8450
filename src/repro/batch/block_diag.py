"""Block-diagonal batching of many ``SparseMatrix`` graphs.

The paper's driving workloads (GNN inference, recommendation) arrive as
streams of *small, variably-shaped* sparse problems.  One kernel launch
per tiny graph leaves the hardware idle between dispatches; the standard
bridge (Gale et al., *Sparse GPU Kernels for Deep Learning*) is to
compose N graphs into one **block-diagonal** operand

    B = diag(A_1, ..., A_N)

so the whole batch runs as a *single* planned SpMM / SDDMM through the
existing dispatch machinery.  Because every stored entry of B lives
inside one diagonal block, B @ H and B.sddmm(b, c) are exact — there is
no cross-graph mixing to correct for.

``BatchedSparseMatrix`` carries the composed ``SparseMatrix`` (CSR
and/or Block-ELL forms, concatenated with index offsets — never via
densification) plus static per-graph ``Segment`` offsets so results
split back out (``unbatch`` / ``unbatch_values``).  Segment metadata is
pytree aux data: jitting a batched product retraces only when the batch
*composition* changes shape, exactly like a single matrix.

Offsets use each graph's **padded** shape (``stats.shape``, a multiple
of the block size) so the element and blocked forms of one batch agree
on where graph i's rows/columns live.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BlockELL, SellCS
from repro.dispatch.stats import MatrixStats
from repro.sparse import paths
from repro.sparse.matrix import FORMATS, SparseMatrix

Array = Any


@dataclasses.dataclass(frozen=True)
class Segment:
    """Where one graph lives inside the batched (block-diagonal) space.

    ``row_start``/``col_start`` are offsets in the *padded* composition;
    ``rows``/``cols`` are the graph's padded extents, ``rows_logical``/
    ``cols_logical`` its true (unpadded) extents.  ``nnz`` and
    ``block_rows``/``ell_width`` drive the per-form value splits.
    """

    row_start: int
    col_start: int
    rows: int
    cols: int
    rows_logical: int
    cols_logical: int
    nnz: int
    block_rows: int
    ell_width: int
    # slot count of the graph's sell form (-1 = not carried): drives the
    # per-graph split of sell values in ``unbatch_values``
    sell_slots: int = -1


def _padded_shape(a: SparseMatrix) -> Tuple[int, int]:
    if a.stats is not None:
        return a.stats.shape
    return a.shape


def _common_formats(mats: Sequence[SparseMatrix]) -> Tuple[str, ...]:
    common = [f for f in FORMATS
              if all(m.has_form(f) for m in mats)]
    return tuple(f for f in ("ell", "sell", "csr") if f in common)


def _concat_csr(mats: Sequence[SparseMatrix],
                segments: Sequence[Segment]):
    rows, cols, vals = [], [], []
    for m, seg in zip(mats, segments):
        r, c, v = m.form("csr")
        rows.append(r + jnp.int32(seg.row_start))
        cols.append(c + jnp.int32(seg.col_start))
        vals.append(v)
    return (jnp.concatenate(rows), jnp.concatenate(cols),
            jnp.concatenate(vals))


def pad_ell_width(indices: Array, blocks: Array, width: int
                  ) -> Tuple[Array, Array]:
    """Widen ELL (indices, blocks) to ``width`` slots per block-row.

    Pad slots point at the row's slot-0 column (any valid id) and carry
    zero data — the Block-ELL padding contract.
    """
    pad = width - indices.shape[1]
    if pad <= 0:
        return indices, blocks
    return (
        jnp.concatenate(
            [indices, jnp.repeat(indices[:, :1], pad, axis=1)], axis=1),
        jnp.concatenate(
            [blocks, jnp.zeros(blocks.shape[:1] + (pad,) + blocks.shape[2:],
                               blocks.dtype)], axis=1),
    )


def _concat_ell(mats: Sequence[SparseMatrix],
                segments: Sequence[Segment],
                shape: Tuple[int, int]) -> BlockELL:
    ells = [m.form("ell") for m in mats]
    bms = {(e.bm, e.bn) for e in ells}
    if len(bms) != 1:
        raise ValueError(
            f"block-diagonal ELL needs one block size, got {sorted(bms)}")
    (bm, bn) = bms.pop()
    width = max(e.ell_width for e in ells)
    indices, blocks, nblocks = [], [], []
    for e, seg in zip(ells, segments):
        idx, blk = pad_ell_width(e.indices, e.blocks, width)
        indices.append(idx + jnp.int32(seg.col_start // bn))
        blocks.append(blk)
        nblocks.append(e.nblocks)
    return BlockELL(indices=jnp.concatenate(indices, axis=0),
                    blocks=jnp.concatenate(blocks, axis=0),
                    nblocks=jnp.concatenate(nblocks, axis=0),
                    shape=shape)


def _concat_sell(mats: Sequence[SparseMatrix],
                 segments: Sequence[Segment],
                 shape: Tuple[int, int]) -> SellCS:
    """Block-diagonal SELL-C-σ composition — pure index arithmetic.

    Each graph keeps its own slice packing (σ-window sorting stays
    per-graph, a valid SELL-C-σ with the graph as the window); slot and
    tile descriptors are concatenated with row/column/slot offsets and
    every sentinel is remapped to the composed sentinel.  No repacking,
    no host transfer of values.
    """
    sells = [m.form("sell") for m in mats]
    blocks = {(s.bm, s.bn) for s in sells}
    if len(blocks) != 1:
        raise ValueError(
            f"block-diagonal sell needs one tile size, got {sorted(blocks)}")
    (bm, bn) = blocks.pop()
    for seg in segments:
        if seg.col_start % bn:
            raise ValueError(
                f"column offset {seg.col_start} not aligned to bn={bn}")
    n_slots_total = sum(s.n_slots for s in sells)
    n_packed_total = sum(s.n_packed_rows for s in sells)
    n_live_total = sum(s.n_live_block_rows for s in sells)
    n_cells_total = sum(s.n_tiles for s in sells) * bm * bn
    m_total, _ = shape

    buckets = []
    slot_cols, slot_rows, slot_vals, perms = [], [], [], []
    tile_rows, tile_cols, tile_maps, slot_pos = [], [], [], []
    out_gather = jnp.full((m_total,), n_packed_total, jnp.int32)
    tile_out_gather = jnp.full((m_total,), n_live_total * bm, jnp.int32)
    row_off = slot_off = live_off = cell_off = 0
    for s, seg in zip(sells, segments):
        m_g = s.shape[0]
        for b_off, b_rows, b_width in s.buckets:
            buckets.append((b_off + row_off, b_rows, b_width))
        slot_cols.append(s.slot_cols + jnp.int32(seg.col_start))
        slot_rows.append(s.slot_rows + jnp.int32(seg.row_start))
        slot_vals.append(s.slot_vals)
        perms.append(jnp.where(s.perm == m_g, jnp.int32(m_total),
                               s.perm + jnp.int32(seg.row_start)))
        tile_rows.append(s.tile_rows + jnp.int32(live_off))
        tile_cols.append(s.tile_cols + jnp.int32(seg.col_start // bn))
        tile_maps.append(jnp.where(
            s.tile_slot_map == s.n_slots, jnp.int32(n_slots_total),
            s.tile_slot_map + jnp.int32(slot_off)))
        slot_pos.append(jnp.where(
            s.slot_tile_pos == s.n_tiles * bm * bn,
            jnp.int32(n_cells_total),
            s.slot_tile_pos + jnp.int32(cell_off)))
        og = jnp.where(s.out_gather == s.n_packed_rows,
                       jnp.int32(n_packed_total),
                       s.out_gather + jnp.int32(row_off))
        out_gather = out_gather.at[
            seg.row_start:seg.row_start + m_g].set(og)
        tog = jnp.where(s.tile_out_gather == s.n_live_block_rows * bm,
                        jnp.int32(n_live_total * bm),
                        s.tile_out_gather + jnp.int32(live_off * bm))
        tile_out_gather = tile_out_gather.at[
            seg.row_start:seg.row_start + m_g].set(tog)
        row_off += s.n_packed_rows
        slot_off += s.n_slots
        live_off += s.n_live_block_rows
        cell_off += s.n_tiles * bm * bn

    return SellCS(
        slot_cols=jnp.concatenate(slot_cols),
        slot_rows=jnp.concatenate(slot_rows),
        slot_vals=jnp.concatenate(slot_vals),
        out_gather=out_gather,
        perm=jnp.concatenate(perms),
        tile_rows=jnp.concatenate(tile_rows),
        tile_cols=jnp.concatenate(tile_cols),
        tile_slot_map=jnp.concatenate(tile_maps, axis=0),
        slot_tile_pos=jnp.concatenate(slot_pos),
        tile_out_gather=tile_out_gather,
        shape=shape,
        c=sells[0].c,
        sigma=sells[0].sigma,
        buckets=tuple(buckets),
        block=(bm, bn),
        n_live_block_rows=n_live_total,
    )


def _combined_stats(mats: Sequence[SparseMatrix],
                    shape: Tuple[int, int]) -> Optional[MatrixStats]:
    stats = [m.stats for m in mats]
    if any(s is None for s in stats):
        return None
    bm = max(s.block_m for s in stats)
    bn = max(s.block_n for s in stats)
    width = max(s.ell_width for s in stats)
    nbr = sum(s.n_block_rows for s in stats)
    stored = sum(s.stored_elements for s in stats)
    # slot-occupancy of the composed layout (streamed slots unchanged:
    # block-diag concatenation adds no padding beyond width alignment)
    occ = sum(s.occupancy * s.n_block_rows * max(s.ell_width, 1)
              for s in stats) / max(nbr * max(width, 1), 1)
    # sell slots concatenate exactly; unknown in any part poisons the sum
    sell_known = all(s.sell_stored_elements > 0 or s.nnz == 0
                     for s in stats)
    # so do live tiles (_concat_sell appends each part's tiles); an
    # unmeasured part leaves the count 0, priced at its upper bound
    tiles_known = all(s.sell_live_tiles > 0 or s.nnz == 0 for s in stats)
    return MatrixStats(
        shape=shape,
        nnz=sum(s.nnz for s in stats),
        stored_elements=stored,
        block_m=bm,
        block_n=bn,
        n_block_rows=nbr,
        ell_width=width,
        occupancy=occ,
        sell_stored_elements=(sum(s.sell_stored_elements for s in stats)
                              if sell_known else 0),
        sell_live_tiles=(sum(s.sell_live_tiles for s in stats)
                         if tiles_known else 0),
    )


@jax.tree_util.register_pytree_node_class
class BatchedSparseMatrix:
    """N sparse graphs composed block-diagonally into one operand.

    ``B.matrix`` is a regular :class:`SparseMatrix` — every planned
    operator (``B @ H``, ``B.sddmm(b, c)``, gradients through both)
    works on the whole batch in one dispatch.  ``B.segments`` records
    the per-graph offsets for ``batch_features`` / ``unbatch``.
    """

    __slots__ = ("matrix", "segments")

    __array_priority__ = 1000
    __array_ufunc__ = None

    def __init__(self, matrix: SparseMatrix,
                 segments: Tuple[Segment, ...]):
        self.matrix = matrix
        self.segments = tuple(segments)

    # -- pytree plumbing ----------------------------------------------------

    def tree_flatten(self):
        return (self.matrix,), self.segments

    @classmethod
    def tree_unflatten(cls, aux, children):
        (matrix,) = children
        return cls(matrix, aux)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_matrices(cls, mats: Sequence[SparseMatrix], *,
                      formats: Optional[Tuple[str, ...]] = None,
                      stats: Optional[MatrixStats] = None,
                      ) -> "BatchedSparseMatrix":
        """Compose N matrices block-diagonally (no densification).

        ``formats`` picks which carried forms to compose (default: every
        form all inputs share, preferring ``("ell", "csr")``); each
        requested form is concatenated with index offsets directly.

        ``stats`` overrides the derived combined stats.  A continuous
        serving lane composes the *same* bucket geometry every step, so
        it computes the canonical combined stats once and passes them
        here — skipping the per-step host reduction and guaranteeing the
        jit aux is byte-identical across steps.
        """
        mats = list(mats)
        if not mats:
            raise ValueError("from_matrices needs at least one matrix")
        if formats is None:
            formats = _common_formats(mats)
            if not formats:
                raise ValueError(
                    "matrices share no common form; convert with .to() "
                    f"first (carried: {[m.formats for m in mats]})")
        for f in formats:
            missing = [i for i, m in enumerate(mats) if not m.has_form(f)]
            if missing:
                raise ValueError(
                    f"matrices {missing} carry no {f!r} form")
        segments: List[Segment] = []
        r0 = c0 = 0
        for m in mats:
            mp, np_ = _padded_shape(m)
            s = m.stats
            segments.append(Segment(
                row_start=r0, col_start=c0, rows=mp, cols=np_,
                rows_logical=m.shape[0], cols_logical=m.shape[1],
                nnz=s.nnz if s is not None else -1,
                block_rows=s.n_block_rows if s is not None else -1,
                ell_width=(m.form("ell").ell_width
                           if m.has_form("ell") else 0),
                sell_slots=(m.form("sell").n_slots
                            if m.has_form("sell") else -1),
            ))
            r0 += mp
            c0 += np_
        shape = (r0, c0)
        forms: Dict[str, Any] = {}
        for f in formats:
            if f == "csr":
                forms["csr"] = _concat_csr(mats, segments)
            elif f == "ell":
                forms["ell"] = _concat_ell(mats, segments, shape)
            elif f == "sell":
                forms["sell"] = _concat_sell(mats, segments, shape)
            else:
                raise ValueError(
                    f"cannot compose {f!r} block-diagonally; supported "
                    "forms: ('ell', 'sell', 'csr')")
        if stats is None:
            stats = _combined_stats(mats, shape)
        elif stats.shape != shape:
            raise ValueError(
                f"stats override has shape {stats.shape} but the "
                f"composition is {shape}")
        matrix = SparseMatrix(forms, shape, stats)
        return cls(matrix, tuple(segments))

    # -- metadata -----------------------------------------------------------

    @property
    def n_graphs(self) -> int:
        return len(self.segments)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.matrix.shape

    @property
    def stats(self):
        return self.matrix.stats

    @property
    def formats(self) -> Tuple[str, ...]:
        return self.matrix.formats

    def __repr__(self) -> str:
        return (f"BatchedSparseMatrix(n_graphs={self.n_graphs}, "
                f"shape={self.shape}, formats={self.formats})")

    # -- feature stacking / result splitting --------------------------------

    def batch_features(self, hs: Sequence[Array]) -> Array:
        """Stack per-graph feature blocks [n_i, d] into the batched
        column space (zero rows fill each graph's block padding)."""
        if len(hs) != self.n_graphs:
            raise ValueError(
                f"got {len(hs)} feature blocks for {self.n_graphs} graphs")
        out = []
        for h, seg in zip(hs, self.segments):
            h = jnp.asarray(h)
            if h.ndim != 2:
                raise ValueError(
                    f"batch_features expects [n_i, d] blocks, got {h.shape}")
            if h.shape[0] != seg.cols_logical:
                raise ValueError(
                    f"feature block has {h.shape[0]} rows; graph has "
                    f"{seg.cols_logical} nodes")
            out.append(paths.pad_rows(h, seg.cols))
        return jnp.concatenate(out, axis=0)

    def unbatch(self, y: Array, *, space: str = "rows") -> List[Array]:
        """Split a batched row-space result (e.g. ``B @ H``) back into
        per-graph arrays, trimming each graph's padding."""
        if space not in ("rows", "cols"):
            raise ValueError(f"space must be 'rows' or 'cols', got {space!r}")
        out = []
        for seg in self.segments:
            if space == "rows":
                out.append(y[seg.row_start:seg.row_start + seg.rows_logical])
            else:
                out.append(y[seg.col_start:seg.col_start + seg.cols_logical])
        return out

    def unbatch_values(self, vals: Array, *, form: Optional[str] = None
                       ) -> List[Array]:
        """Split a batched values leaf (``B.matrix.data``, an SDDMM
        result, or a gradient w.r.t. the batched values) per graph.

        ``form`` names the layout the values are in (default: the
        batch's primary form).  Element (csr) values split by per-graph
        nnz; Block-ELL values split by block-rows with each graph's
        width padding trimmed back off.
        """
        form = form or self.matrix.format
        if form == "csr":
            if any(seg.nnz < 0 for seg in self.segments):
                raise ValueError(
                    "cannot split element values: a graph was composed "
                    "without stats (unknown nnz)")
            sizes = [seg.nnz for seg in self.segments]
            offs = np.cumsum([0] + sizes)
            return [vals[offs[i]:offs[i + 1]] for i in range(len(sizes))]
        if form == "ell":
            if any(seg.block_rows < 0 for seg in self.segments):
                raise ValueError(
                    "cannot split blocked values: a graph was composed "
                    "without stats (unknown block-row count)")
            width = self.matrix.form("ell").ell_width
            out = []
            row = 0
            for seg in self.segments:
                blk = vals[row:row + seg.block_rows]
                out.append(blk[:, :seg.ell_width] if seg.ell_width < width
                           else blk)
                row += seg.block_rows
            return out
        if form == "sell":
            if any(seg.sell_slots < 0 for seg in self.segments):
                raise ValueError(
                    "cannot split sell values: a graph was composed "
                    "without a sell form (unknown slot count)")
            offs = np.cumsum([0] + [seg.sell_slots
                                    for seg in self.segments])
            return [vals[offs[i]:offs[i + 1]]
                    for i in range(self.n_graphs)]
        raise ValueError(f"cannot split values of form {form!r}")

    # -- batched operators --------------------------------------------------

    def __matmul__(self, h):
        return self.matrix @ h

    def __rmatmul__(self, x):
        return x @ self.matrix

    def matmul(self, h, **kw):
        from repro.sparse import ops

        return ops.matmul(self.matrix, h, **kw)

    def sddmm(self, b, c, **kw) -> SparseMatrix:
        """Batched ``B ⊙ (b @ c)`` — one planned SDDMM for the batch."""
        return self.matrix.sddmm(b, c, **kw)


def batch_matmul(mats: Sequence[SparseMatrix], hs: Sequence[Array], *,
                 formats: Optional[Tuple[str, ...]] = None,
                 **kw) -> List[Array]:
    """One-shot helper: block-diag compose, run one SpMM, split back."""
    B = BatchedSparseMatrix.from_matrices(mats, formats=formats)
    y = B.matmul(B.batch_features(hs), **kw)
    return B.unbatch(y)


def batch_sddmm(B: BatchedSparseMatrix, bs: Sequence[Array],
                cs: Sequence[Array], **kw) -> List[Array]:
    """Batched attention scoring: one SDDMM over the block-diagonal
    composition, split back into per-graph sampled values.

    ``bs[i]``: [m_i, K] row factors; ``cs[i]``: [K, n_i] column factors.
    Because every stored entry of B is inside a diagonal block, the
    batched sample equals each graph's ``A_i ⊙ (b_i @ c_i)`` exactly.
    """
    if len(bs) != B.n_graphs or len(cs) != B.n_graphs:
        raise ValueError(
            f"got {len(bs)}/{len(cs)} factor blocks for {B.n_graphs} graphs")
    brows = []
    for b, seg in zip(bs, B.segments):
        b = jnp.asarray(b)
        if b.shape[0] != seg.rows_logical:
            raise ValueError(
                f"row factor has {b.shape[0]} rows; graph has "
                f"{seg.rows_logical}")
        brows.append(paths.pad_rows(b, seg.rows))
    ccols = []
    for c, seg in zip(cs, B.segments):
        c = jnp.asarray(c)
        if c.shape[1] != seg.cols_logical:
            raise ValueError(
                f"column factor has {c.shape[1]} columns; graph has "
                f"{seg.cols_logical}")
        ccols.append(paths.pad_cols(c, seg.cols))
    s = B.sddmm(jnp.concatenate(brows, axis=0),
                jnp.concatenate(ccols, axis=1), **kw)
    return B.unbatch_values(s.data, form=s.format)
