"""Analytic cost model for SpMM/SDDMM path selection.

Costs are *relative*: each path's cost is (elements it must stream and
multiply) x (a per-element cost constant).  The constants encode the
hardware asymmetry the paper measures:

  * the dense path runs the MXU flat out but touches every element
    (``c_dense`` = 1.0 per element, the unit);
  * the blocked streaming path (Block-ELL / Block-COO) also feeds the
    MXU but pays gather/index overhead and computes its *padding*
    (``c_ell`` slightly above 1.0, applied to stored-including-padding
    elements — the paper's padded-stream volume);
  * the element-level CSR/COO path does exact nnz work but retires ~one
    MAC per scalar op instead of a full MXU lane (``c_csr`` >> 1,
    applied to true nonzeros only).

The paper's crossover falls out directly: the streaming path wins while
its padded-stream blow-up (stored/nnz) stays below ``c_csr / c_ell``;
beyond ~99% sparsity the blow-up explodes past that ratio and the scalar
path takes over (Fig. 9's hyper-sparsity cliff).

The sell path is priced by the executor that will run it.  The jnp
reference (and SpMV's sell lane, always) works slot by slot:
``c_sell`` x packed slots.  The Pallas kernels walk the live tiles of
the packing and multiply each one densely on the MXU, the Block-ELL
walk's tile product: ``c_ell`` x live tiles x bm x bn.  At low tile fill
(a skewed graph's 64x64 tiles hold a few slots each) that is three
orders of magnitude above the slot count, and the cliff reappears
inside sell itself.  Where the crossover falls on a given chip is
measured (``autotune.calibrate`` at the probe shapes), not assumed:
``DEVICE_COST_MODELS`` holds the constants fitted on each device kind,
and the shipped constants stay for every other backend.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.dispatch.policy import PATH_CSR, PATH_DENSE, PATH_ELL, PATH_SELL
from repro.dispatch.stats import MatrixStats


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-element relative cost constants (unitless, dense == 1.0)."""

    c_dense: float = 1.0
    # stored-element cost of the blocked path: MXU-fed but pays the
    # index gather; > c_dense so a fully-dense matrix prefers `dense`.
    c_ell: float = 1.05
    # per-nonzero cost of the scalar path: no MXU, one lane of work per
    # element.  c_csr / c_ell is the padded-stream blow-up at which the
    # scalar path overtakes the streaming path (the paper's crossover).
    c_csr: float = 12.0
    # per-slot cost of the SELL-C-σ slot executor (the jnp reference,
    # SpMV's sell lane): gather-granular like csr, but scatter-free
    # (slice-local dense reduction) and load-balanced, so each slot is
    # cheaper than a csr nonzero.  Applied to the packed slot volume
    # (real + slice padding): where the Block-ELL blow-up explodes past
    # c_sell/c_ell, sell takes over instead of falling off the cliff;
    # where the matrix is dense enough for blocked streaming (blow-up
    # below ~c_sell/c_ell), ell still wins.  The sell kernels are priced
    # by their tiles at c_ell instead (module doc).
    c_sell: float = 9.0

    def spmm_costs(self, stats: MatrixStats, d: int, *,
                   sell_tiles: bool = False) -> Dict[str, float]:
        """Relative cost of Y[M,D] = A[M,N] @ H[N,D] per path.

        The ELL path is priced off ``ell_stream_estimate`` — stored
        volume floored by M x max_row_nnz — so a hub-heavy matrix whose
        global density looks streaming-friendly is still charged for
        the width its heaviest row forces on every row.  ``sell_tiles``:
        the sell path runs the tile-walk kernel (see the module doc).
        """
        d = max(int(d), 1)
        return {
            PATH_DENSE: self.c_dense * stats.dense_elements * d,
            PATH_ELL: self.c_ell * stats.ell_stream_estimate * d,
            PATH_SELL: self._sell_cost(stats, d, sell_tiles),
            PATH_CSR: self.c_csr * stats.nnz * d,
        }

    def sddmm_costs(self, stats: MatrixStats, k: int, *,
                    sell_tiles: bool = False) -> Dict[str, float]:
        """Relative cost of Y = A (.) (B[M,K] @ C[K,N]) per path."""
        k = max(int(k), 1)
        return {
            PATH_DENSE: self.c_dense * stats.dense_elements * k,
            PATH_ELL: self.c_ell * stats.stored_elements * k,
            PATH_SELL: self._sell_cost(stats, k, sell_tiles),
            PATH_CSR: self.c_csr * stats.nnz * k,
        }

    def fused_attn_costs(self, stats: MatrixStats, k: int, d: int, *,
                         sell_tiles: bool = False) -> Dict[str, float]:
        """Relative cost of the one-pass fused attention pipeline.

        The unfused SDDMM→softmax→SpMM composition streams the topology
        three times (score it, normalize it, aggregate with it); the
        fused kernel streams every live tile exactly once, doing the
        k-wide score dot and the d-wide V accumulation while the tile is
        resident — so each path is priced at ONE stream of its layout's
        stored volume at the combined inner width ``k + d``.
        """
        inner = max(int(k), 1) + max(int(d), 1)
        return {
            PATH_DENSE: self.c_dense * stats.dense_elements * inner,
            PATH_ELL: self.c_ell * stats.ell_stream_estimate * inner,
            PATH_SELL: self._sell_cost(stats, inner, sell_tiles),
            PATH_CSR: self.c_csr * stats.nnz * inner,
        }

    def _sell_cost(self, stats: MatrixStats, inner: int,
                   sell_tiles: bool) -> float:
        # sell_stored_elements == 0 with nonzeros present means the slot
        # volume was never measured (e.g. stats built from a transposed
        # operand): the path is unpriceable, never auto-picked.
        if stats.sell_stored_elements <= 0 and stats.nnz > 0:
            return float("inf")
        if sell_tiles:
            return self.c_ell * stats.sell_tile_elements * inner
        return self.c_sell * stats.sell_stored_elements * inner

    @staticmethod
    def pick(costs: Dict[str, float]) -> str:
        """Cheapest path; ties broken dense < ell < sell < csr."""
        order = {PATH_DENSE: 0, PATH_ELL: 1, PATH_SELL: 2, PATH_CSR: 3}
        return min(costs, key=lambda p: (costs[p], order[p]))


DEFAULT_COST_MODEL = CostModel()

# Constants fitted on the chip by ``autotune.calibrate``, by
# ``jax.Device.device_kind``; ``dispatcher.default_cost_model`` picks
# the running device's entry.
#
# TPU v5e: calibrate(n=4096, d=128) over 90 / 95 / 99 / 99.9% sparsity,
# Pallas kernels, 64x64 blocks.  The sell kernel's ratios (by tile
# volume) pool into c_ell; c_sell keeps the shipped constant, since the
# slot executor it prices (SpMV's sell lane) was not timed.  The csr
# path's gather + segment_sum costs ~93 dense MXU elements a nonzero,
# so it overtakes the tiled paths near 1% density, not 8.75%.
DEVICE_COST_MODELS: Dict[str, CostModel] = {
    "TPU v5 lite": CostModel(c_dense=1.0, c_ell=0.9293, c_csr=92.99,
                             c_sell=9.0),
}
