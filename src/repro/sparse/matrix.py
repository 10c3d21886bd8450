"""`SparseMatrix` — one differentiable array type over every format.

A ``SparseMatrix`` wraps the repo's sparse storage formats behind one
pytree-registered interface:

  * ``"csr"`` — element-granular (row_ids, col_ids, values) device
    arrays, int32 indices (the expanded-CSR form every scalar path
    consumes);
  * ``"ell"`` — :class:`repro.core.formats.BlockELL` (the SELLPACK-like
    blocked streaming layout);
  * ``"sell"`` — :class:`repro.core.formats.SellCS` (SELL-C-σ: rows
    sorted by nnz within σ-windows, width-adaptive slices, live tiles
    only — the hyper-sparsity path);
  * ``"coo"`` — :class:`repro.core.formats.BlockCOO` (the SDDMM-side
    blocked layout, and the layout Block-ELL transposes into).

A matrix may carry several forms at once (e.g. a GNN adjacency holds
``("ell", "csr")`` so the dispatcher can route either path at jit trace
time).  Device data are pytree children; everything the planner needs —
logical shape, the format list, host-measured :class:`MatrixStats`, and
the per-instance plan memo — is static aux metadata, so ``jax.jit`` of
``lambda A, H: A @ H`` retraces only when shape/format/structure change,
never per call.

Operators: ``A @ H`` dispatches SpMM, ``A.sddmm(b, c)`` (or
``repro.sparse.sample``) dispatches SDDMM, ``A.T`` transposes (Block-ELL
transposes into Block-COO without host work, so it is trace-safe), and
both products are differentiable — see ``repro.sparse.autodiff`` for
the SpMM <-> SDDMM gradient duality.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.formats import CSR, BlockCOO, BlockELL, SellCS
from repro.dispatch.cost_model import CostModel
from repro.dispatch.dispatcher import plan_spmm
from repro.dispatch.policy import PATH_CSR, PATH_SELL
from repro.dispatch.stats import MatrixStats
from repro.sparse import paths
from repro.sparse.plan import PlanCache

Array = Any

FORMATS = ("ell", "sell", "coo", "csr")
# feature width assumed when from_dense(format="auto") prices the paths
_AUTO_FORMAT_D = 256  # the paper's SpMM setting (§4.1)

# Densified-form memo for concrete matrices, keyed on the id of the
# values leaf with a weakref finalizer for eviction (jax arrays are
# weakref-able but unhashable).  custom_vjp re-unflattens its pytree
# arguments (a fresh SparseMatrix per call), but the underlying array
# objects are passed through — so an instance-level memo would never
# hit, while this one survives reconstruction and dies with the array.
_DENSE_MEMO: Dict[int, Tuple[Tuple[int, ...], Any, Any]] = {}


def _leaf_ids(form) -> Tuple[int, ...]:
    return tuple(id(x) for x in jax.tree_util.tree_leaves(form))


def _dense_memo_get(vkey, form):
    hit = _DENSE_MEMO.get(id(vkey))
    if hit is not None and hit[0] == _leaf_ids(form):
        return hit[1]
    return None


def _dense_memo_put(vkey, form, out) -> None:
    k = id(vkey)
    try:
        wr = weakref.ref(vkey, lambda _ref: _DENSE_MEMO.pop(k, None))
    except TypeError:  # un-weakref-able leaf type (e.g. plain numpy)
        return
    _DENSE_MEMO[k] = (_leaf_ids(form), out, wr)


def _is_traced(*leaves) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in leaves)


def values_of(name: str, form) -> Array:
    """The differentiable data leaf of one form."""
    if name == "csr":
        return form[2]
    if name == "sell":
        return form.slot_vals
    return form.blocks


def with_values(name: str, form, vals: Array):
    """Same topology, new data leaf."""
    if name == "csr":
        return (form[0], form[1], vals)
    if name == "ell":
        return BlockELL(indices=form.indices, blocks=vals,
                        nblocks=form.nblocks, shape=form.shape)
    if name == "sell":
        return dataclasses.replace(form, slot_vals=vals)
    return BlockCOO(rows=form.rows, cols=form.cols, blocks=vals,
                    shape=form.shape)


def _blocked_stats(shape: Tuple[int, int], rows: np.ndarray,
                   cols: np.ndarray, bm: int, bn: int,
                   nnz: int) -> MatrixStats:
    """Blocked-layout stats from element coordinates (no blocks built)."""
    return MatrixStats.from_coords(shape, rows, cols, block_m=bm,
                                   block_n=bn, nnz=nnz)


def _transpose_stats(stats: Optional[MatrixStats]) -> Optional[MatrixStats]:
    if stats is None:
        return None
    bm, bn = stats.block_n, stats.block_m
    return MatrixStats(
        shape=(stats.shape[1], stats.shape[0]),
        nnz=stats.nnz,
        stored_elements=stats.stored_elements,
        block_m=bm,
        block_n=bn,
        n_block_rows=max(stats.shape[1] // max(bm, 1), 1),
        ell_width=0,
        occupancy=stats.occupancy,
    )


@jax.tree_util.register_pytree_node_class
class SparseMatrix:
    """One sparse matrix, any storage format, dispatch-ready.

    Construct with :meth:`from_dense` / :meth:`from_csr` /
    :meth:`from_blockell` / :meth:`from_blockcoo`; do not call the
    constructor with raw forms unless you know the pytree contract.
    """

    __slots__ = ("_forms", "shape", "stats", "_cache", "_transpose")

    # make `np_array @ A` defer to __rmatmul__ instead of numpy coercion
    __array_priority__ = 1000
    __array_ufunc__ = None

    def __init__(self, forms: Dict[str, Any], shape: Tuple[int, int],
                 stats: Optional[MatrixStats],
                 cache: Optional[PlanCache] = None):
        if not forms:
            raise ValueError("SparseMatrix needs at least one form")
        for name in forms:
            if name not in FORMATS:
                raise ValueError(
                    f"unknown format {name!r}; expected one of {FORMATS}")
        self._forms = dict(forms)
        self.shape = (int(shape[0]), int(shape[1]))
        self.stats = stats
        self._cache = cache if cache is not None else PlanCache()
        self._transpose: Optional["SparseMatrix"] = None

    # -- pytree plumbing ----------------------------------------------------

    def tree_flatten(self):
        names = tuple(self._forms)
        children = tuple(self._forms[n] for n in names)
        return children, (names, self.shape, self.stats, self._cache)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, shape, stats, cache = aux
        return cls(dict(zip(names, children)), shape, stats, cache=cache)

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_dense(cls, a, *, format: str = "auto",
                   formats: Optional[Tuple[str, ...]] = None,
                   block: Tuple[int, int] = (64, 64),
                   ell_width: Optional[int] = None,
                   cost_model: Optional[CostModel] = None,
                   ) -> "SparseMatrix":
        """Build from a concrete dense matrix.

        ``format="auto"`` measures the operand's blocked structure and
        packs the form of the path the dispatcher would plan for an
        SpMM on this backend: the element form when the scalar path wins
        (hyper-sparsity), sell when its executor here is cheapest, the
        blocked form otherwise.  ``formats`` overrides with an explicit
        multi-form tuple.
        """
        if _is_traced(a):
            raise TypeError(
                "SparseMatrix.from_dense needs a concrete (host) matrix; "
                "construct outside jit and pass the SparseMatrix in")
        a = np.asarray(a)
        if a.ndim != 2:
            raise ValueError(f"expected a 2-D matrix, got shape {a.shape}")
        bm, bn = block
        with obs.span("sparse.stats"):
            rows, cols = np.nonzero(a)
            stats = _blocked_stats(a.shape, rows, cols, bm, bn,
                                   nnz=len(rows))
        if formats is None:
            if format == "auto":
                pick = plan_spmm(stats, _AUTO_FORMAT_D,
                                 cost_model=cost_model).path
                format = {PATH_CSR: "csr", PATH_SELL: "sell"}.get(pick,
                                                                  "ell")
            formats = (format,)
        forms: Dict[str, Any] = {}
        for name in formats:
            if name not in FORMATS:
                raise ValueError(
                    f"unknown format {name!r}; expected one of {FORMATS}")
            with obs.span(f"sparse.pack.{name}"):
                if name == "ell":
                    forms[name] = BlockELL.from_dense(a, bm, bn,
                                                      ell_width=ell_width)
                elif name == "sell":
                    forms[name] = SellCS.from_dense(a, block=block)
                elif name == "coo":
                    forms[name] = BlockCOO.from_dense(a, bm, bn)
                else:
                    forms[name] = (
                        jnp.asarray(rows.astype(np.int32)),
                        jnp.asarray(cols.astype(np.int32)),
                        jnp.asarray(a[rows, cols]),
                    )
        return cls(forms, a.shape, stats)

    @classmethod
    def from_csr(cls, csr: CSR, *, block: Tuple[int, int] = (64, 64)
                 ) -> "SparseMatrix":
        bm, bn = block
        row_ids, col_ids, vals = paths.csr_to_device_arrays(csr)
        stats = _blocked_stats(csr.shape, np.asarray(row_ids),
                               np.asarray(col_ids), bm, bn, nnz=csr.nnz)
        return cls({"csr": (row_ids, col_ids, vals)}, csr.shape, stats)

    @classmethod
    def from_blockell(cls, ell: BlockELL, *,
                      stats: Optional[MatrixStats] = None,
                      nnz: Optional[int] = None) -> "SparseMatrix":
        """Wrap an existing BlockELL.  For traced input pass ``stats``
        explicitly (or leave None and force a path at dispatch time)."""
        if stats is None and not _is_traced(ell.blocks, ell.indices):
            stats = MatrixStats.from_blockell(ell, nnz=nnz)
        return cls({"ell": ell}, ell.shape, stats)

    @classmethod
    def from_blockcoo(cls, coo: BlockCOO, *,
                      stats: Optional[MatrixStats] = None,
                      nnz: Optional[int] = None) -> "SparseMatrix":
        if stats is None and not _is_traced(coo.blocks, coo.rows):
            stats = MatrixStats.from_blockcoo(coo, nnz=nnz)
        return cls({"coo": coo}, coo.shape, stats)

    @classmethod
    def from_sellcs(cls, sell: SellCS, *,
                    stats: Optional[MatrixStats] = None) -> "SparseMatrix":
        """Wrap an existing SELL-C-σ packing (concrete input computes
        stats host-side; traced input needs ``stats`` or a forced path)."""
        if stats is None and not _is_traced(sell.slot_vals,
                                            sell.slot_rows):
            mask = np.asarray(sell.slot_vals) != 0
            rows = np.asarray(sell.slot_rows)[mask]
            cols = np.asarray(sell.slot_cols)[mask]
            stats = _blocked_stats(sell.shape, rows, cols,
                                   sell.bm, sell.bn, nnz=len(rows))
        return cls({"sell": sell}, sell.shape, stats)

    # -- basic metadata -----------------------------------------------------

    @property
    def format(self) -> str:
        """Primary format (the one ``.data`` / ``with_data`` address)."""
        return next(iter(self._forms))

    @property
    def formats(self) -> Tuple[str, ...]:
        return tuple(self._forms)

    def has_form(self, name: str) -> bool:
        return name in self._forms

    def form(self, name: str):
        """The raw container of one carried form."""
        if name not in self._forms:
            raise ValueError(
                f"matrix carries no {name!r} form (has {self.formats}); "
                "convert with .to()")
        return self._forms[name]

    @property
    def ndim(self) -> int:
        return 2

    @property
    def plan_cache(self) -> PlanCache:
        """This instance's plan memo (carries per-matrix hit/miss
        counters; see ``PlanCache.stats``)."""
        return self._cache

    @property
    def data(self) -> Array:
        """Differentiable values leaf of the primary form."""
        return values_of(self.format, self._forms[self.format])

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nnz(self) -> int:
        if self.stats is None:
            raise ValueError("matrix has no sparsity stats")
        return self.stats.nnz

    @property
    def density(self) -> float:
        if self.stats is None:
            raise ValueError("matrix has no sparsity stats")
        return self.stats.density

    @property
    def block(self) -> Tuple[int, int]:
        if self.stats is not None:
            return (self.stats.block_m, self.stats.block_n)
        return (64, 64)

    def nbytes(self) -> int:
        return sum(
            int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(self._forms))

    def __repr__(self) -> str:
        nnz = self.stats.nnz if self.stats is not None else "?"
        return (f"SparseMatrix(shape={self.shape}, formats={self.formats}, "
                f"nnz={nnz})")

    # -- data / topology edits ----------------------------------------------

    def with_data(self, values: Array) -> "SparseMatrix":
        """Same topology, new values on the *primary* form.

        Secondary forms are dropped (their values would go stale); the
        plan memo is shared — plans depend on structure, not values.
        """
        name = self.format
        form = with_values(name, self._forms[name], values)
        return SparseMatrix({name: form}, self.shape, self.stats,
                            cache=self._cache)

    def with_stats(self, stats: MatrixStats) -> "SparseMatrix":
        """Same forms and data, re-stated planner stats, fresh plan memo.

        Serving overlays (``repro.serve.runtime.DeltaGraph``) use this
        at repack/re-pricing boundaries: the stats are pytree aux, so a
        re-stat deliberately changes the jit cache key — the planner
        re-prices the matrix and consumers retrace once.  The plan memo
        is NOT shared (unlike :meth:`with_data`): memoized plans were
        priced off the old stats.
        """
        if stats is not None and (stats.shape[0] < self.shape[0]
                                  or stats.shape[1] < self.shape[1]):
            raise ValueError(
                f"stats shape {stats.shape} does not cover matrix shape "
                f"{self.shape} (stats carry the padded extent)")
        return SparseMatrix(self._forms, self.shape, stats)

    @jax.named_scope("sparse.layout.pattern")
    def pattern(self) -> "SparseMatrix":
        """0/1 mask of the primary form's nonzero entries (the sampling
        operand SDDMM and the backward pass work on)."""
        v = self.data
        return self.with_data(jnp.where(v != 0, jnp.ones_like(v),
                                        jnp.zeros_like(v)))

    def with_form(self, fmt: str) -> "SparseMatrix":
        """This matrix plus one more carried form (lazy: a no-op when
        ``fmt`` is already carried; host conversion otherwise).

        The added form makes its execution path a dispatch candidate;
        the plan memo is shared — plan keys include the candidate set,
        so cached plans stay correct.
        """
        if fmt in self._forms:
            return self
        with obs.span(f"sparse.pack.{fmt}"):
            converted = self.to(fmt)
        forms = dict(self._forms)
        forms[fmt] = converted._forms[fmt]
        return SparseMatrix(forms, self.shape, self.stats,
                            cache=self._cache)

    # -- transpose ----------------------------------------------------------

    @property
    def T(self) -> "SparseMatrix":
        if self._transpose is None:
            self._transpose = self._transposed()
            self._transpose._transpose = self
        return self._transpose

    def _transposed(self) -> "SparseMatrix":
        forms: Dict[str, Any] = {}
        for name, form in self._forms.items():
            if name == "csr":
                r, c, v = form
                forms["csr"] = (c, r, v)
            elif name == "sell":
                # a packed tile covers permuted (non-contiguous) rows,
                # so sell transposes element-granularly: the slot triplet
                # with coordinates swapped IS the transposed csr form
                # (duplicate padding coordinates carry zero values)
                forms.setdefault(
                    "csr", (form.slot_cols, form.slot_rows, form.slot_vals))
            else:
                coo = paths.ell_to_coo(form) if name == "ell" else form
                forms.setdefault("coo", paths.transpose_coo(coo))
        return SparseMatrix(forms, (self.shape[1], self.shape[0]),
                            _transpose_stats(self.stats))

    # -- conversions --------------------------------------------------------

    def densify(self) -> Array:
        """Dense jnp array (trace-safe device scatter from the primary
        form), trimmed to the logical shape.

        Memoized for concrete matrices so repeated dense-path dispatch
        pays the scatter once (traced leaves are never memoized — the
        result would capture another trace's tracers).
        """
        name = self.format
        form = self._forms[name]
        leaves = jax.tree_util.tree_leaves(form)
        concrete = not _is_traced(*leaves)
        vkey = values_of(name, form)
        if concrete:
            hit = _dense_memo_get(vkey, form)
            if hit is not None:
                return hit
        m, n = self.shape
        if name == "csr":
            out = paths.densify_elements(form[0], form[1], form[2], (m, n))
        elif name == "sell":
            out = paths.densify_sell(form)
        else:
            full = paths.densify_ell(form) if name == "ell" \
                else paths.densify_coo(form)
            out = full[:m, :n]
        if concrete and not isinstance(out, jax.core.Tracer):
            _dense_memo_put(vkey, form, out)
        return out

    def to_dense(self) -> np.ndarray:
        """Host numpy densification (concrete matrices only)."""
        return np.asarray(self.densify())

    def to(self, fmt: str) -> Any:
        """Convert to another format.

        Returns a (single-form) ``SparseMatrix`` for ``"ell"/"coo"/
        "csr"`` — reusing device arrays when the form is already carried
        — or a dense jnp array for ``"dense"``.  Host-side conversion of
        a missing form requires a concrete matrix.
        """
        if fmt == "dense":
            return self.densify()
        if fmt not in FORMATS:
            raise ValueError(
                f"unknown format {fmt!r}; expected 'dense' or {FORMATS}")
        if fmt in self._forms:
            return SparseMatrix({fmt: self._forms[fmt]}, self.shape,
                                self.stats, cache=self._cache)
        if _is_traced(*jax.tree_util.tree_leaves(self._forms)):
            raise TypeError(
                f"cannot convert a traced matrix to {fmt!r}; convert "
                "outside jit (only carried forms are trace-safe)")
        dense = self.to_dense()
        bm, bn = self.block
        if fmt == "ell":
            return SparseMatrix({"ell": BlockELL.from_dense(dense, bm, bn)},
                                self.shape, self.stats, cache=self._cache)
        if fmt == "sell":
            return SparseMatrix(
                {"sell": SellCS.from_dense(dense, block=(bm, bn))},
                self.shape, self.stats, cache=self._cache)
        if fmt == "coo":
            return SparseMatrix({"coo": BlockCOO.from_dense(dense, bm, bn)},
                                self.shape, self.stats, cache=self._cache)
        rows, cols = np.nonzero(dense)
        form = (jnp.asarray(rows.astype(np.int32)),
                jnp.asarray(cols.astype(np.int32)),
                jnp.asarray(dense[rows, cols]))
        return SparseMatrix({"csr": form}, self.shape, self.stats,
                            cache=self._cache)

    # -- operators ----------------------------------------------------------

    def __matmul__(self, h):
        if isinstance(h, SparseMatrix):
            return NotImplemented
        from repro.sparse import ops

        return ops.matmul(self, h)

    def matmul(self, h, *, epilogue=None, bias=None, residual=None, **kw):
        """``A @ H`` with an optional fused epilogue.

        ``A.matmul(h, epilogue="relu", bias=b)`` computes
        ``relu(A @ h + b)`` with the elementwise tail fused into the
        SpMM (applied to the kernel accumulator before the output
        flush).  See :func:`repro.sparse.ops.matmul`.
        """
        from repro.sparse import ops

        return ops.matmul(self, h, epilogue=epilogue, bias=bias,
                          residual=residual, **kw)

    def __rmatmul__(self, x):
        from repro.sparse import ops

        x = jnp.asarray(x)
        if x.ndim == 1:
            return ops.matmul(self.T, x)
        if x.ndim != 2:
            return NotImplemented
        return ops.matmul(self.T, x.T).T

    def sddmm(self, b, c, **kw) -> "SparseMatrix":
        """``self ⊙ (b @ c)`` at this matrix's stored entries."""
        from repro.sparse import ops

        return ops.sddmm(self, b, c, **kw)
