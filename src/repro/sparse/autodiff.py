"""custom_vjp rules wiring the paper's two kernels into each other.

SpMM and SDDMM are transpose/backward duals (Gale et al., *Sparse GPU
Kernels for Deep Learning*): for ``Y = A @ H``,

  * ``dH = Aᵀ @ ḡ``            — another SpMM, on the transposed operand;
  * ``dA = pattern(A) ⊙ (ḡ Hᵀ)`` — exactly SDDMM sampled on A's nonzero
    topology.

and for ``S = A ⊙ (B C)``,

  * ``dA = ḡ ⊙ (B C)``          — elementwise on the stored values;
  * ``dB = (A ⊙ ḡ) @ Cᵀ``       — an SpMM with the cotangent-weighted A;
  * ``dC = ((A ⊙ ḡ)ᵀ @ B)ᵀ``    — the transposed SpMM.

Each rule executes through the same path the forward ran (ell / csr /
dense) and records its decision in the dispatch log, so the duality is
observable: after a backward pass ``dispatch_log()`` contains the
partner op's plan.

Scopes: each path executor names the device ops it emits
``sparse.<op>.<path>`` and each backward rule ``sparse.vjp.<op>``
(``jax.named_scope``: HLO metadata only), so a profile ties every op to
the product, path and rule that issued it.

Gradient semantics: cotangents flow to the *stored values* of the form
the forward pass read; structural zeros (padding slots, element zeros)
receive zero gradient so SGD can never resurrect pruned entries.
Integer topology arrays get ``float0`` cotangents.  Secondary forms of
a multi-form matrix were not read by the forward computation, so their
values correctly receive zero.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BlockCOO
from repro.dispatch.dispatcher import Plan, record_plan
from repro.dispatch.policy import (PATH_CSR, PATH_DENSE, PATH_ELL,
                                   PATH_SELL)
from repro.kernels.fused.epilogue import (Epilogue, act_grad_from_out,
                                          apply_epilogue)
from repro.sparse import paths
from repro.sparse.matrix import SparseMatrix, values_of, with_values

# cfg: (path, use_kernel, interpret, bd_or_bk, out_dtype_str) — hashable,
# resolved by the planner in ops.py before the differentiable call.
Cfg = Tuple[str, bool, bool, Optional[int], Optional[str]]
# epilogue cfg: Cfg + (Epilogue,) — the fused-SpMM variant.
EpiCfg = Tuple[str, bool, bool, Optional[int], Optional[str], Epilogue]
# attention cfg: (path, use_kernel, interpret, act, slope, out_dtype_str)
AttnCfg = Tuple[str, bool, bool, str, float, Optional[str]]


def _dispatch_scope(op: str):
    """Name every device op a path executor emits ``sparse.<op>.<path>``
    (``cfg[0]`` is the planned path), so a profile ties each op to the
    product and path that issued it."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(cfg, *args):
            with jax.named_scope(f"sparse.{op}.{cfg[0]}"):
                return fn(cfg, *args)
        return run
    return wrap


def _float0_like(x):
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


def _cotangent_like(a: SparseMatrix, form_name: str,
                    dvals) -> SparseMatrix:
    """A-structured cotangent: dvals on ``form_name``'s values leaf,
    zeros on other forms' values, float0 on integer topology arrays."""
    forms = {}
    for name, form in a._forms.items():
        v = values_of(name, form)
        dv = dvals if name == form_name else jnp.zeros_like(v)
        if name == "csr":
            forms[name] = (_float0_like(form[0]), _float0_like(form[1]), dv)
        elif name == "ell":
            forms[name] = type(form)(
                indices=_float0_like(form.indices), blocks=dv,
                nblocks=_float0_like(form.nblocks), shape=form.shape)
        elif name == "sell":
            forms[name] = type(form)(
                slot_cols=_float0_like(form.slot_cols),
                slot_rows=_float0_like(form.slot_rows),
                slot_vals=dv,
                out_gather=_float0_like(form.out_gather),
                perm=_float0_like(form.perm),
                tile_rows=_float0_like(form.tile_rows),
                tile_cols=_float0_like(form.tile_cols),
                tile_slot_map=_float0_like(form.tile_slot_map),
                slot_tile_pos=_float0_like(form.slot_tile_pos),
                tile_out_gather=_float0_like(form.tile_out_gather),
                shape=form.shape, c=form.c, sigma=form.sigma,
                buckets=form.buckets, block=form.block,
                n_live_block_rows=form.n_live_block_rows)
        else:
            forms[name] = type(form)(
                rows=_float0_like(form.rows), cols=_float0_like(form.cols),
                blocks=dv, shape=form.shape)
    return SparseMatrix(forms, a.shape, a.stats, cache=a._cache)


def form_read_by(a: SparseMatrix, path: str) -> str:
    """Which carried form a given execution path reads."""
    if path == PATH_CSR:
        return "csr"
    if path == PATH_ELL:
        return "ell" if "ell" in a._forms else "coo"
    if path == PATH_SELL:
        # the transpose of a sell operand carries the slot triplet as an
        # element form; the sell path falls back to it (see spmm_exec)
        return "sell" if "sell" in a._forms else "csr"
    return a.format  # dense path densifies the primary form


# ---------------------------------------------------------------------------
# Path execution (shared by forward and both backward rules)
# ---------------------------------------------------------------------------


@_dispatch_scope("spmm")
def spmm_exec(cfg: Cfg, a: SparseMatrix, h):
    """Run one planned SpMM path; h: [N, D] logical rows; returns [M, D]."""
    path, use_kernel, interpret, bd, out_dtype = cfg
    m = a.shape[0]
    if path == PATH_ELL:
        if "ell" in a._forms:
            ell = a._forms["ell"]
            y = paths.spmm_ell(ell, paths.pad_rows(h, ell.shape[1]),
                               use_kernel=use_kernel, interpret=interpret,
                               bd=bd, out_dtype=out_dtype)
        else:
            coo = a._forms["coo"]
            y = paths.spmm_coo(coo, paths.pad_rows(h, coo.shape[1]),
                               out_dtype=out_dtype)
        return y[:m]
    if path == PATH_SELL:
        if "sell" in a._forms:
            return paths.spmm_sell(a._forms["sell"], h,
                                   use_kernel=use_kernel,
                                   interpret=interpret, bd=bd,
                                   out_dtype=out_dtype)
        # transposed sell operand: the slot triplet is an element form
        r, c, v = a.form("csr")
        y = paths.spmm_elements(r, c, v, h, m)
        return y.astype(out_dtype) if out_dtype else y
    if path == PATH_CSR:
        r, c, v = a.form("csr")
        y = paths.spmm_elements(r, c, v, h, m)
        return y.astype(out_dtype) if out_dtype else y
    if path == PATH_DENSE:
        y = paths.spmm_dense(a.densify(), h)
        return y.astype(out_dtype) if out_dtype else y
    raise ValueError(f"unknown spmm path {path!r}")


@_dispatch_scope("spmv")
def spmv_exec(cfg: Cfg, a: SparseMatrix, x):
    """Run one planned SpMV path; x: [N] logical entries; returns [M].

    The vector fast lane: same path vocabulary as SpMM, but each layout
    runs a direct reduction (see paths.spmv_*) instead of the [N, 1]
    tile pipeline.  ``bd`` in cfg is ignored — there is no D to tile.
    """
    path, _use_kernel, _interpret, _bd, out_dtype = cfg
    m = a.shape[0]
    if path == PATH_ELL:
        if "ell" in a._forms:
            ell = a._forms["ell"]
            y = paths.spmv_ell(ell, paths.pad_rows(x, ell.shape[1]),
                               out_dtype=out_dtype)
        else:
            coo = a._forms["coo"]
            y = paths.spmv_coo(coo, paths.pad_rows(x, coo.shape[1]),
                               out_dtype=out_dtype)
        return y[:m]
    if path == PATH_SELL:
        if "sell" in a._forms:
            return paths.spmv_sell(a._forms["sell"], x,
                                   out_dtype=out_dtype)
        r, c, v = a.form("csr")  # transposed sell: slot triplet
        y = paths.spmv_elements(r, c, v, x, m)
        return y.astype(out_dtype) if out_dtype else y
    if path == PATH_CSR:
        r, c, v = a.form("csr")
        y = paths.spmv_elements(r, c, v, x, m)
        return y.astype(out_dtype) if out_dtype else y
    if path == PATH_DENSE:
        y = paths.spmm_dense(a.densify(), x)
        return y.astype(out_dtype) if out_dtype else y
    raise ValueError(f"unknown spmv path {path!r}")


@_dispatch_scope("sddmm")
def sample_exec(cfg: Cfg, a: SparseMatrix, b, c):
    """Raw sampled dots (B @ C at A's stored slots), in the layout of the
    form the path reads — the unweighted SDDMM the backward rules share."""
    path, use_kernel, interpret, bk, _ = cfg
    form_name = form_read_by(a, path)
    form = a._forms[form_name]
    if path == PATH_CSR:
        return paths.sddmm_element_dots(form[0], form[1], b, c)
    if path == PATH_SELL:
        if form_name == "sell":
            return paths.sample_sell(form, b, c, use_kernel=use_kernel,
                                     interpret=interpret, bk=bk)
        return paths.sddmm_element_dots(form[0], form[1], b, c)
    if path == PATH_ELL:
        coo = paths.ell_to_coo(form) if form_name == "ell" else form
        ones = BlockCOO(rows=coo.rows, cols=coo.cols,
                        blocks=jnp.ones_like(coo.blocks), shape=coo.shape)
        out = paths.sddmm_blocked(
            ones, paths.pad_rows(b, coo.shape[0]),
            paths.pad_cols(c, coo.shape[1]),
            use_kernel=use_kernel, interpret=interpret, bk=bk).blocks
        if form_name == "ell":
            return out.reshape(form.blocks.shape)
        return out
    if path == PATH_DENSE:
        full = b.astype(jnp.float32) @ c.astype(jnp.float32)
        if form_name == "csr":
            return full[form[0], form[1]].astype(b.dtype)
        if form_name == "sell":
            return full[form.slot_rows, form.slot_cols].astype(b.dtype)
        coo = paths.ell_to_coo(form) if form_name == "ell" else form
        full = paths.pad_cols(paths.pad_rows(full, coo.shape[0]),
                              coo.shape[1])
        out = paths.sample_blocks(full, coo.rows, coo.cols,
                                  coo.bm, coo.bn).astype(b.dtype)
        if form_name == "ell":
            return out.reshape(form.blocks.shape)
        return out
    raise ValueError(f"unknown sddmm path {path!r}")


def _mask_structural(vals, grad):
    """Zero the gradient at structural zeros (padding, pruned entries)."""
    return jnp.where(vals != 0, grad, jnp.zeros_like(grad)) \
        .astype(vals.dtype)


def _record_vjp(op: str, path: str, reason: str, cfg: Cfg) -> None:
    record_plan(Plan(op=op, path=path, policy="vjp", reason=reason,
                     use_kernel=bool(cfg[1]), interpret=bool(cfg[2])))


# ---------------------------------------------------------------------------
# SpMM: Y = A @ H
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def spmm(cfg: Cfg, a: SparseMatrix, h):
    return spmm_exec(cfg, a, h)


def _spmm_fwd(cfg: Cfg, a: SparseMatrix, h):
    return spmm_exec(cfg, a, h), (a, h)


@jax.named_scope("sparse.vjp.spmm")
def _spmm_bwd(cfg: Cfg, res, g):
    path = cfg[0]
    a, h = res
    # dH = Aᵀ @ ḡ : SpMM on the transposed operand, same path (Block-ELL
    # transposes into Block-COO, which the blocked path also executes).
    dh = spmm_exec((path, cfg[1], cfg[2], None, None), a.T, g)
    _record_vjp("spmm", path, "vjp: dH = Aᵀ @ ḡ (spmm backward)", cfg)
    # dA = pattern(A) ⊙ (ḡ @ Hᵀ) : SDDMM on A's nonzero topology.
    form_name = form_read_by(a, path)
    raw = sample_exec((path, cfg[1], cfg[2], None, None), a, g, h.T)
    _record_vjp("sddmm", path,
                "vjp: dA = pattern(A) ⊙ (ḡ @ Hᵀ) (spmm backward is sddmm)",
                cfg)
    vals = values_of(form_name, a._forms[form_name])
    da = _cotangent_like(a, form_name, _mask_structural(vals, raw))
    return da, dh.astype(h.dtype)


spmm.defvjp(_spmm_fwd, _spmm_bwd)


# ---------------------------------------------------------------------------
# SpMV: y = A @ x  (vector fast lane; same duality at d = 1)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def spmv(cfg: Cfg, a: SparseMatrix, x):
    return spmv_exec(cfg, a, x)


def _spmv_fwd(cfg: Cfg, a: SparseMatrix, x):
    return spmv_exec(cfg, a, x), (a, x)


@jax.named_scope("sparse.vjp.spmv")
def _spmv_bwd(cfg: Cfg, res, g):
    path = cfg[0]
    a, x = res
    # dx = Aᵀ @ ḡ : another SpMV, on the transposed operand.
    dx = spmv_exec((path, cfg[1], cfg[2], None, None), a.T, g)
    _record_vjp("spmv", path, "vjp: dx = Aᵀ @ ḡ (spmv backward)", cfg)
    # dA = pattern(A) ⊙ (ḡ xᵀ) : rank-1 SDDMM on A's topology.
    form_name = form_read_by(a, path)
    raw = sample_exec((path, cfg[1], cfg[2], None, None), a,
                      g[:, None], x[None, :])
    _record_vjp("sddmm", path,
                "vjp: dA = pattern(A) ⊙ (ḡ xᵀ) (spmv backward is sddmm)",
                cfg)
    vals = values_of(form_name, a._forms[form_name])
    da = _cotangent_like(a, form_name, _mask_structural(vals, raw))
    return da, dx.astype(x.dtype)


spmv.defvjp(_spmv_fwd, _spmv_bwd)


# ---------------------------------------------------------------------------
# SDDMM: S = A ⊙ (B @ C)  (values in the layout of the form the path reads)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def sddmm_values(cfg: Cfg, a: SparseMatrix, b, c):
    return _sddmm_fwd(cfg, a, b, c)[0]


@_dispatch_scope("sddmm")
def _sddmm_fwd(cfg: Cfg, a: SparseMatrix, b, c):
    raw = sample_exec(cfg, a, b, c)
    form_name = form_read_by(a, cfg[0])
    vals = values_of(form_name, a._forms[form_name])
    out = vals.astype(jnp.float32) * raw.astype(jnp.float32)
    out_dtype = cfg[4] or jnp.result_type(vals.dtype, b.dtype)
    return out.astype(out_dtype), (a, b, c, raw)


@jax.named_scope("sparse.vjp.sddmm")
def _sddmm_bwd(cfg: Cfg, res, g):
    path = cfg[0]
    a, b, c, raw = res
    form_name = form_read_by(a, path)
    vals = values_of(form_name, a._forms[form_name])
    # dA = ḡ ⊙ (B C) sampled — elementwise on the stored values.
    dvals = _mask_structural(
        vals, g.astype(jnp.float32) * raw.astype(jnp.float32))
    da = _cotangent_like(a, form_name, dvals)
    # M = A ⊙ ḡ shares A's topology; both remaining grads are SpMMs.
    mg = (vals.astype(jnp.float32) * g.astype(jnp.float32))
    m_mat = SparseMatrix(
        {form_name: with_values(form_name, a._forms[form_name],
                                mg.astype(vals.dtype))},
        a.shape, a.stats, cache=a._cache)
    exec_cfg = (path, cfg[1], cfg[2], None, None)
    db = spmm_exec(exec_cfg, m_mat, c.T)
    _record_vjp("spmm", path, "vjp: dB = (A ⊙ ḡ) @ Cᵀ (sddmm backward is "
                "spmm)", cfg)
    dc = spmm_exec(exec_cfg, m_mat.T, b).T
    _record_vjp("spmm", path, "vjp: dC = ((A ⊙ ḡ)ᵀ @ B)ᵀ (sddmm backward "
                "is spmm)", cfg)
    return da, db.astype(b.dtype), dc.astype(c.dtype)


sddmm_values.defvjp(_sddmm_fwd, _sddmm_bwd)


# ---------------------------------------------------------------------------
# Fused SpMM + epilogue: Y = act(A @ H + bias + residual)
# ---------------------------------------------------------------------------


@_dispatch_scope("spmm")
def spmm_epilogue_exec(cfg: EpiCfg, a: SparseMatrix, h, bias, residual):
    """Run one planned SpMM path with its epilogue fused.

    The blocked kernel routes (Block-ELL / SELL-C-σ on the kernel path)
    apply the epilogue to the VMEM accumulator at the flush; every other
    route composes the reference SpMM with the elementwise tail, which
    XLA fuses — semantics are identical either way.
    """
    path, use_kernel, interpret, bd, out_dtype, epi = cfg
    kernelish = use_kernel or interpret
    if kernelish and path == PATH_ELL and "ell" in a._forms:
        from repro.kernels.fused.spmm import spmm_blockell_fused

        ell = a._forms["ell"]
        y = spmm_blockell_fused(
            ell, paths.pad_rows(h, ell.shape[1]), epi, bias, residual,
            bd=bd, out_dtype=out_dtype, use_kernel=use_kernel,
            interpret=interpret)
        return y[: a.shape[0]]
    if kernelish and path == PATH_SELL and "sell" in a._forms:
        from repro.kernels.fused.spmm import spmm_sell_fused

        return spmm_sell_fused(
            a._forms["sell"], h, epi, bias, residual, bd=bd,
            out_dtype=out_dtype, use_kernel=use_kernel,
            interpret=interpret)
    y = spmm_exec((path, use_kernel, interpret, bd, out_dtype), a, h)
    return apply_epilogue(y, epi, bias, residual)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def spmm_epilogue(cfg: EpiCfg, a: SparseMatrix, h, bias, residual):
    return spmm_epilogue_exec(cfg, a, h, bias, residual)


def _spmm_epilogue_fwd(cfg: EpiCfg, a: SparseMatrix, h, bias, residual):
    out = spmm_epilogue_exec(cfg, a, h, bias, residual)
    # the activation derivative is recoverable from the output sign
    # (relu/leaky_relu preserve it), so `out` is the only extra residual
    return out, (a, h, bias, residual, out)


@jax.named_scope("sparse.vjp.spmm")
def _spmm_epilogue_bwd(cfg: EpiCfg, res, g):
    path, use_kernel, interpret = cfg[0], cfg[1], cfg[2]
    epi = cfg[5]
    a, h, bias, residual, out = res
    dz = g.astype(jnp.float32) * act_grad_from_out(
        out.astype(jnp.float32), epi.act, epi.negative_slope)
    dbias = None
    if epi.has_bias:
        # ops.matmul canonicalizes bias to [D], so the cotangent is the
        # row reduction reshaped to the operand's (validated) shape
        dbias = dz.sum(axis=0).reshape(jnp.shape(bias)).astype(bias.dtype)
    dres = dz.astype(residual.dtype) if epi.has_residual else None
    # past the elementwise tail the rules are exactly the SpMM duality
    exec_cfg = (path, use_kernel, interpret, None, None)
    dh = spmm_exec(exec_cfg, a.T, dz)
    _record_vjp("spmm", path,
                "vjp: dH = Aᵀ @ (ḡ ⊙ act') (fused-epilogue spmm backward)",
                cfg)
    form_name = form_read_by(a, path)
    raw = sample_exec(exec_cfg, a, dz, h.T)
    _record_vjp("sddmm", path,
                "vjp: dA = pattern(A) ⊙ ((ḡ ⊙ act') @ Hᵀ) (fused-epilogue "
                "spmm backward is sddmm)", cfg)
    vals = values_of(form_name, a._forms[form_name])
    da = _cotangent_like(a, form_name, _mask_structural(vals, raw))
    return da, dh.astype(h.dtype), dbias, dres


spmm_epilogue.defvjp(_spmm_epilogue_fwd, _spmm_epilogue_bwd)


# ---------------------------------------------------------------------------
# Fused graph attention: Y = softmax_row(act(q kᵀ ⊙ pattern(A))) @ V
# ---------------------------------------------------------------------------


def _edge_act_grad(raw, act: str, slope: float):
    """d act/ds at the raw sampled scores."""
    if act == "identity":
        return jnp.ones_like(raw)
    if act == "relu":
        return jnp.where(raw > 0, 1.0, 0.0)
    if act == "leaky_relu":
        return jnp.where(raw >= 0, 1.0, slope)
    raise ValueError(f"unknown edge activation {act!r}")


def _form_broadcast_rows(a: SparseMatrix, form_name: str, vec):
    """Broadcast a per-logical-row vector onto a form's values layout."""
    form = a._forms[form_name]
    if form_name == "csr":
        return vec[form[0]]
    if form_name == "sell":
        return vec[form.slot_rows]
    bm = form.bm
    padded = paths.pad_rows(vec, form.shape[0])
    by_row = padded.reshape(-1, bm)  # [nbr, bm]
    if form_name == "ell":
        return by_row[:, None, :, None]   # -> [nbr, W, bm, bn] broadcast
    return by_row[form.rows][:, :, None]  # coo: [nnzb, bm, 1]


def _form_row_softmax(a: SparseMatrix, form_name: str, e, mask):
    """Row softmax of masked scores ``e`` laid out like one form's values.

    ``e`` is float32 with masked (structural-zero) entries already at
    NEG_INF; the result carries exact zeros there.  Matches
    ``models.gnn._segment_softmax`` (same 1e-12 denominator guard).
    """
    from repro.kernels.fused.attention import EPS

    form = a._forms[form_name]
    m = a.shape[0]
    if form_name in ("csr", "sell"):
        rows = form[0] if form_name == "csr" else form.slot_rows
        mx = jax.ops.segment_max(e, rows, num_segments=m)
        ex = jnp.where(mask, jnp.exp(e - mx[rows]), 0.0)
        den = jax.ops.segment_sum(ex, rows, num_segments=m)
        return ex / jnp.maximum(den[rows], EPS)
    if form_name == "ell":
        mx = e.max(axis=(1, 3))  # [nbr, bm]
        ex = jnp.where(mask, jnp.exp(e - mx[:, None, :, None]), 0.0)
        den = ex.sum(axis=(1, 3))
        return ex / jnp.maximum(den, EPS)[:, None, :, None]
    # coo: segment over block rows
    nbr = form.shape[0] // form.bm
    mx = jax.ops.segment_max(e.max(axis=2), form.rows, num_segments=nbr)
    ex = jnp.where(mask, jnp.exp(e - mx[form.rows][:, :, None]), 0.0)
    den = jax.ops.segment_sum(ex.sum(axis=2), form.rows, num_segments=nbr)
    return ex / jnp.maximum(den[form.rows][:, :, None], EPS)


@_dispatch_scope("attention")
def fused_attention_exec(cfg: AttnCfg, a: SparseMatrix, q, k, v):
    """One-pass SDDMM→edge-act→softmax→SpMM over A's structural nonzeros.

    ``q``: [M, dk] and ``k``: [N, dk] score factors (scores = q @ kᵀ
    sampled at A's pattern), ``v``: [N, D] values.  A's stored *values*
    only contribute their nonzero pattern.
    """
    from repro.kernels.fused import attention as fat

    path, use_kernel, interpret, act, slope, out_dtype = cfg
    m = a.shape[0]
    kt = k.T
    if path == PATH_ELL:
        if "ell" in a._forms:
            y = fat.fused_attn_blockell(
                a._forms["ell"], q, kt, v, act=act, slope=slope,
                out_dtype=out_dtype, use_kernel=use_kernel,
                interpret=interpret)
            return y[:m]
        coo = a._forms["coo"]
        return fat.fused_attn_blockcoo_ref(
            coo, paths.pad_rows(q, coo.shape[0]),
            paths.pad_cols(kt, coo.shape[1]),
            paths.pad_rows(v, coo.shape[1]),
            act=act, slope=slope,
            out_dtype=out_dtype or jnp.result_type(q.dtype, v.dtype))[:m]
    if path == PATH_SELL:
        if "sell" in a._forms:
            return fat.fused_attn_sell(
                a._forms["sell"], q, kt, v, act=act, slope=slope,
                out_dtype=out_dtype, use_kernel=use_kernel,
                interpret=interpret)
        r, c, vals = a.form("csr")  # transposed sell: slot triplet
        return fat.fused_attn_elements(r, c, vals, q, kt, v, m, act=act,
                                       slope=slope, out_dtype=out_dtype)
    if path == PATH_CSR:
        r, c, vals = a.form("csr")
        return fat.fused_attn_elements(r, c, vals, q, kt, v, m, act=act,
                                       slope=slope, out_dtype=out_dtype)
    if path == PATH_DENSE:
        return fat.fused_attn_dense(a.densify(), q, kt, v, act=act,
                                    slope=slope, out_dtype=out_dtype)
    raise ValueError(f"unknown fused-attention path {path!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_attention(cfg: AttnCfg, a: SparseMatrix, q, k, v):
    return fused_attention_exec(cfg, a, q, k, v)


def _fused_attention_fwd(cfg: AttnCfg, a: SparseMatrix, q, k, v):
    out = fused_attention_exec(cfg, a, q, k, v)
    return out, (a, q, k, v, out)


@jax.named_scope("sparse.vjp.attention")
def _fused_attention_bwd(cfg: AttnCfg, res, g):
    """The fused pipeline's backward, assembled from the kernel duality.

    With α = softmax(act(e)) and O = α V:

      * dV = αᵀ ḡ                      — SpMM on the transposed α;
      * dα = ḡ Vᵀ sampled at pattern   — SDDMM;
      * softmax JVP trick: de' = α ⊙ (dα - rowdot), where
        rowdot_i = ḡ_i · O_i re-uses the forward output instead of a
        second α-weighted reduction;
      * de = de' ⊙ act'(e); then dq = (P ⊙ de) k and dk = (P ⊙ de)ᵀ q
        — the SDDMM backward's two SpMMs.

    α and the raw scores are recomputed in the forward layout (one
    SDDMM + a row softmax), so the forward never has to spill them.
    """
    path, use_kernel, interpret, act, slope, _ = cfg
    a, q, k, v, out = res
    exec_cfg = (path, use_kernel, interpret, None, None)
    form_name = form_read_by(a, path)
    form = a._forms[form_name]
    vals = values_of(form_name, form)
    mask = vals != 0

    from repro.kernels.fused.attention import NEG_INF
    from repro.kernels.fused.epilogue import apply_act

    raw = sample_exec(exec_cfg, a, q, k.T).astype(jnp.float32)
    _record_vjp("sddmm", path,
                "vjp: recompute e = act(q kᵀ) at pattern (fused attn "
                "backward)", cfg)
    e = jnp.where(mask, apply_act(raw, act, slope), NEG_INF)
    alpha = _form_row_softmax(a, form_name, e, mask)

    dalpha = sample_exec(exec_cfg, a, g, v.T).astype(jnp.float32)
    _record_vjp("sddmm", path,
                "vjp: dα = ḡ Vᵀ at pattern (fused attn backward is sddmm)",
                cfg)
    rowdot = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    rd = _form_broadcast_rows(a, form_name, rowdot)
    de = alpha * (dalpha - rd) * _edge_act_grad(raw, act, slope)
    de = jnp.where(mask, de, 0.0)

    de_mat = SparseMatrix(
        {form_name: with_values(form_name, form, de.astype(vals.dtype))},
        a.shape, a.stats, cache=a._cache)
    dq = spmm_exec(exec_cfg, de_mat, k)
    _record_vjp("spmm", path,
                "vjp: dq = (P ⊙ de) k (fused attn backward is spmm)", cfg)
    dk = spmm_exec(exec_cfg, de_mat.T, q)
    _record_vjp("spmm", path,
                "vjp: dk = (P ⊙ de)ᵀ q (fused attn backward is spmm)", cfg)
    alpha_mat = SparseMatrix(
        {form_name: with_values(form_name, form,
                                alpha.astype(vals.dtype))},
        a.shape, a.stats, cache=a._cache)
    dv = spmm_exec(exec_cfg, alpha_mat.T, g)
    _record_vjp("spmm", path,
                "vjp: dV = αᵀ ḡ (fused attn backward is spmm)", cfg)
    # attention reads only A's nonzero *pattern*; its stored values get
    # zero cotangent (structure is not differentiable)
    da = _cotangent_like(a, form_name, jnp.zeros_like(vals))
    return da, dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)
