"""Unified `repro.sparse.SparseMatrix` API: operators, pytree/jit
behavior, plan caching, and the SpMM <-> SDDMM gradient duality.

This file must stay clean under ``-W error::DeprecationWarning`` (CI
runs it that way).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dispatch.dispatcher import clear_log, dispatch_log, last_plan
from repro.sparse import (SparseMatrix, matmul, plan_cache_stats, sample,
                          sddmm, spmv)

SWEEP = [0.5, 0.9, 0.99]
N, D = 128, 16
BLOCK = (16, 16)

# (dispatch path, format that can execute it) — covers all four paths
PATH_FORMATS = [("ell", "ell"), ("ell", "coo"), ("csr", "csr"),
                ("sell", "sell"), ("dense", "ell"), ("dense", "csr"),
                ("dense", "sell")]


def _uniform_sparse(rng, n, sparsity):
    mask = rng.random((n, n)) < (1.0 - sparsity)
    return np.where(mask, rng.normal(size=(n, n)), 0.0).astype(np.float32)


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(7)
    out = {}
    for s in SWEEP:
        dense = _uniform_sparse(rng, N, s)
        out[s] = dense
    return out


@pytest.fixture
def h(rng):
    return jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))


# ---------------------------------------------------------------------------
# construction, conversion, operators
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["ell", "sell", "coo", "csr"])
def test_roundtrip_and_matmul_every_format(operands, h, fmt):
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, format=fmt, block=BLOCK)
    assert A.format == fmt and A.shape == (N, N)
    np.testing.assert_array_equal(A.to_dense(), dense)
    y = A @ h
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)


def test_auto_format_follows_measured_structure(operands):
    # moderate sparsity -> blocked form; hyper-sparsity -> sell packing
    assert SparseMatrix.from_dense(operands[0.5], block=BLOCK).format \
        == "ell"
    rng = np.random.default_rng(3)
    hyper = _uniform_sparse(rng, 256, 0.999)
    assert SparseMatrix.from_dense(hyper, block=(4, 4)).format == "sell"


def test_conversion_table(operands):
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, format="ell", block=BLOCK)
    for fmt in ("ell", "sell", "coo", "csr"):
        B = A.to(fmt)
        assert B.format == fmt
        np.testing.assert_array_equal(B.to_dense(), dense)
    np.testing.assert_array_equal(np.asarray(A.to("dense")), dense)


def test_multiform_carries_both_paths(operands, h):
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, formats=("ell", "csr"), block=BLOCK)
    assert A.formats == ("ell", "csr")
    ys = {p: np.asarray(matmul(A, h, policy=p))
          for p in ("ell", "csr", "dense")}
    for y in ys.values():
        np.testing.assert_allclose(y, dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)


def test_transpose_and_rmatmul(operands, h):
    dense = operands[0.9]
    for fmt in ("ell", "sell", "csr", "coo"):
        A = SparseMatrix.from_dense(dense, format=fmt, block=BLOCK)
        np.testing.assert_allclose(np.asarray(A.T @ h),
                                   dense.T @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
        x = np.asarray(h).T  # [D, N]
        np.testing.assert_allclose(np.asarray(x @ A), x @ dense,
                                   rtol=2e-4, atol=2e-4)
    assert A.T.T is A  # transpose is memoized and involutive


def test_matmul_1d_and_shape_errors(operands):
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, format="ell", block=BLOCK)
    v = np.ones(N, np.float32)
    np.testing.assert_allclose(np.asarray(A @ v), dense @ v,
                               rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="rows but A has"):
        A @ np.ones((N - 4, D), np.float32)
    with pytest.raises(ValueError, match="not among available paths"):
        matmul(SparseMatrix.from_dense(dense, format="csr"), v,
               policy="ell")


def test_sddmm_operator(operands, rng):
    dense = operands[0.9]
    mask = (dense != 0).astype(np.float32)
    b = jnp.asarray(rng.normal(size=(N, 4)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(4, N)).astype(np.float32))
    oracle = mask * np.asarray(b @ c)
    for fmt, path in (("coo", "ell"), ("csr", "csr"), ("ell", "dense")):
        M = SparseMatrix.from_dense(mask, format=fmt, block=BLOCK)
        S = sddmm(M, b, c, policy=path)
        np.testing.assert_allclose(S.to_dense(), oracle,
                                   rtol=2e-4, atol=2e-4)
        assert last_plan("sddmm").path == path
    # weighted sampling: values multiply the product (A ⊙ (B C))
    W = SparseMatrix.from_dense(dense, format="csr")
    np.testing.assert_allclose(W.sddmm(b, c).to_dense(),
                               dense * np.asarray(b @ c),
                               rtol=2e-4, atol=2e-4)


def test_csr_indices_are_int32_end_to_end(operands):
    from repro.core.formats import CSR

    dense = operands[0.9]
    csr = CSR.from_dense(dense)
    assert csr.indptr.dtype == np.int32
    assert csr.indices.dtype == np.int32
    A = SparseMatrix.from_dense(dense, formats=("ell", "coo", "csr"),
                                block=BLOCK)
    r, c, _ = A.form("csr")
    assert r.dtype == jnp.int32 and c.dtype == jnp.int32
    assert A.form("ell").indices.dtype == jnp.int32
    assert A.form("coo").rows.dtype == jnp.int32


# ---------------------------------------------------------------------------
# pytree / jit behavior
# ---------------------------------------------------------------------------


def test_pytree_roundtrip(operands):
    A = SparseMatrix.from_dense(operands[0.9], formats=("ell", "csr"),
                                block=BLOCK)
    leaves, treedef = jax.tree_util.tree_flatten(A)
    B = jax.tree_util.tree_unflatten(treedef, leaves)
    assert B.formats == A.formats and B.shape == A.shape
    assert B.stats == A.stats
    np.testing.assert_array_equal(B.to_dense(), A.to_dense())


def test_jit_retraces_only_on_shape_or_format_change(operands, h):
    traces = []

    def f(A, H):
        traces.append(1)
        return A @ H

    jf = jax.jit(f)
    A = SparseMatrix.from_dense(operands[0.9], format="ell", block=BLOCK)
    y1 = jf(A, h)
    jf(A, h)
    assert len(traces) == 1, "same instance must not retrace per call"
    # same structure (equal stats), fresh instance: still no retrace
    A2 = SparseMatrix.from_dense(operands[0.9].copy(), format="ell",
                                 block=BLOCK)
    jf(A2, h)
    assert len(traces) == 1, "equal-structure operand must reuse the trace"
    np.testing.assert_allclose(np.asarray(y1),
                               operands[0.9] @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)
    # format change -> retrace
    jf(A.to("csr"), h)
    assert len(traces) == 2
    # shape change -> retrace
    rng = np.random.default_rng(5)
    small = _uniform_sparse(rng, 64, 0.9)
    jf(SparseMatrix.from_dense(small, format="ell", block=BLOCK),
       jnp.asarray(np.ones((64, D), np.float32)))
    assert len(traces) == 3


def test_jit_matmul_matches_eager(operands, h):
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, formats=("ell", "csr"), block=BLOCK)
    jf = jax.jit(lambda a, hh: matmul(a, hh, policy="auto"))
    np.testing.assert_allclose(np.asarray(jf(A, h)),
                               np.asarray(matmul(A, h, policy="auto")),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# plan caching
# ---------------------------------------------------------------------------


def test_plan_cache_hits_on_repeated_calls(operands, h):
    A = SparseMatrix.from_dense(operands[0.9], format="ell", block=BLOCK)
    before = plan_cache_stats()
    A @ h
    mid = plan_cache_stats()
    assert mid["misses"] == before["misses"] + 1
    for _ in range(3):
        A @ h
    after = plan_cache_stats()
    assert after["hits"] >= mid["hits"] + 3
    assert after["misses"] == mid["misses"], "re-planned on a cached call"
    # width change is a different key -> one more planning pass
    A @ jnp.ones((N, 2 * D), jnp.float32)
    assert plan_cache_stats()["misses"] == after["misses"] + 1


def test_plan_cache_shared_through_with_data(operands, h):
    A = SparseMatrix.from_dense(operands[0.9], format="csr")
    A @ h
    stats0 = plan_cache_stats()
    A.with_data(A.data * 2.0) @ h  # same topology -> plan memo reused
    stats1 = plan_cache_stats()
    assert stats1["misses"] == stats0["misses"]
    assert stats1["hits"] == stats0["hits"] + 1


def test_plan_cache_per_instance_counters(operands, h):
    A = SparseMatrix.from_dense(operands[0.9], format="csr")
    B = SparseMatrix.from_dense(operands[0.5], format="csr")
    A @ h
    for _ in range(3):
        A @ h
    sa = A.plan_cache.stats()
    assert sa["misses"] == 1 and sa["hits"] == 3 and sa["entries"] == 1
    # another instance's traffic never moves this instance's counters
    B @ h
    assert A.plan_cache.stats() == sa
    assert B.plan_cache.stats()["misses"] == 1


# ---------------------------------------------------------------------------
# densified-form memo: weakref eviction
# ---------------------------------------------------------------------------


def test_dense_memo_entry_dies_with_values_array(operands, h):
    import gc

    from repro.sparse import matrix as matrix_mod

    A = SparseMatrix.from_dense(operands[0.9], format="csr")
    key = id(A.data)
    d1 = A.densify()
    assert key in matrix_mod._DENSE_MEMO
    assert A.densify() is d1, "second densify must hit the memo"
    del A, d1
    gc.collect()
    assert key not in matrix_mod._DENSE_MEMO, \
        "memo entry must die with its values array"


def test_dense_memo_no_growth_across_build_drop_cycles(operands, h):
    import gc

    from repro.sparse import matrix as matrix_mod

    gc.collect()
    base = len(matrix_mod._DENSE_MEMO)
    for fmt in ("csr", "ell", "coo"):
        for _ in range(4):
            A = SparseMatrix.from_dense(operands[0.9], format=fmt,
                                        block=BLOCK)
            np.testing.assert_allclose(
                np.asarray(A.densify() @ h),
                operands[0.9] @ np.asarray(h), rtol=2e-4, atol=2e-4)
            del A
            gc.collect()
    assert len(matrix_mod._DENSE_MEMO) == base, \
        "repeated from_dense/densify/drop cycles must not grow the memo"


# ---------------------------------------------------------------------------
# gradients: the kernels are each other's backward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("path,fmt", PATH_FORMATS)
def test_spmm_grads_match_dense_autodiff(operands, h, sparsity, path, fmt):
    dense = operands[sparsity]
    A = SparseMatrix.from_dense(dense, format=fmt, block=BLOCK)
    w = jnp.asarray(np.linspace(-1, 1, D, dtype=np.float32))

    def sparse_loss(vals, hh):
        return jnp.sum(jnp.tanh(matmul(A.with_data(vals), hh,
                                       policy=path)) * w)

    def dense_loss(ad, hh):
        return jnp.sum(jnp.tanh(ad @ hh) * w)

    gv, gh = jax.grad(sparse_loss, argnums=(0, 1))(A.data, h)
    g_ad, g_hd = jax.grad(dense_loss, argnums=(0, 1))(jnp.asarray(dense), h)
    # dH agrees everywhere
    np.testing.assert_allclose(np.asarray(gh), np.asarray(g_hd),
                               rtol=1e-5, atol=1e-5)
    # dA agrees at the true nonzeros (structural zeros stay zero)
    g_sparse = A.with_data(gv).to_dense()
    mask = dense != 0
    np.testing.assert_allclose(g_sparse[mask], np.asarray(g_ad)[mask],
                               rtol=1e-5, atol=1e-5)
    assert (g_sparse[~mask] == 0).all(), "gradient resurrected a zero"


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("path,fmt", [("ell", "coo"), ("csr", "csr"),
                                      ("sell", "sell"), ("dense", "coo")])
def test_sddmm_grads_match_dense_autodiff(operands, rng, sparsity, path,
                                          fmt):
    dense = operands[sparsity]
    mask = (dense != 0).astype(np.float32)
    M = SparseMatrix.from_dense(mask, format=fmt, block=BLOCK)
    b = jnp.asarray(rng.normal(size=(N, 4)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(4, N)).astype(np.float32))

    def sparse_loss(bb, cc):
        return jnp.sum(jnp.sin(sddmm(M, bb, cc, policy=path).densify()))

    def dense_loss(bb, cc):
        return jnp.sum(jnp.sin(jnp.asarray(mask) * (bb @ cc)))

    gb, gc = jax.grad(sparse_loss, argnums=(0, 1))(b, c)
    gb_d, gc_d = jax.grad(dense_loss, argnums=(0, 1))(b, c)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gc), np.asarray(gc_d),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("path", ["ell", "csr", "dense"])
def test_gcn_loss_grad_matches_dense_reference(operands, rng, path):
    """Acceptance: jax.grad of a GCN loss through A @ H matches the
    dense reference to 1e-5 on every dispatch path."""
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, formats=("ell", "csr"), block=BLOCK)
    f_in, f_hid, f_out = 8, 12, 4
    w1 = jnp.asarray(rng.normal(size=(f_in, f_hid)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(f_hid, f_out)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(N, f_in)).astype(np.float32))
    labels = jnp.asarray((np.arange(N) % f_out).astype(np.int32))

    def gcn_loss(params, agg):
        h = agg(x @ params[0])
        h = jax.nn.relu(h)
        logits = agg(h @ params[1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

    g_sparse = jax.grad(gcn_loss)(
        (w1, w2), lambda t: matmul(A, t, policy=path))
    g_dense = jax.grad(gcn_loss)(
        (w1, w2), lambda t: jnp.asarray(dense) @ t)
    for gs, gd in zip(g_sparse, g_dense):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gd),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("path", ["ell", "sell", "csr", "dense"])
def test_spmm_backward_routes_through_sddmm_dispatcher(operands, h, path):
    """Acceptance: the SpMM backward provably runs as an SDDMM (and the
    dH half as an SpMM on Aᵀ), visible in the dispatch log."""
    fmt = {"csr": "csr", "sell": "sell"}.get(path, "ell")
    A = SparseMatrix.from_dense(operands[0.9], format=fmt, block=BLOCK)
    clear_log()
    jax.grad(lambda v, hh: jnp.sum(matmul(A.with_data(v), hh,
                                          policy=path) ** 2),
             argnums=(0, 1))(A.data, h)
    vjp = [(p.op, p.path) for p in dispatch_log() if p.policy == "vjp"]
    assert ("sddmm", path) in vjp, vjp  # dA = pattern(A) ⊙ (ḡ Hᵀ)
    assert ("spmm", path) in vjp, vjp   # dH = Aᵀ @ ḡ


def test_sddmm_backward_routes_through_spmm_dispatcher(operands, rng):
    mask = (operands[0.9] != 0).astype(np.float32)
    M = SparseMatrix.from_dense(mask, format="csr")
    b = jnp.asarray(rng.normal(size=(N, 2)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(2, N)).astype(np.float32))
    clear_log()
    jax.grad(lambda bb: jnp.sum(sample(M, bb, c, policy="csr").data ** 2))(b)
    vjp = [(p.op, p.path) for p in dispatch_log() if p.policy == "vjp"]
    assert vjp.count(("spmm", "csr")) == 2, vjp  # dB and dC


def test_jit_grad_traces_cleanly(operands, h):
    """Acceptance: jax.jit(jax.grad(...)) through the custom_vjp."""
    A = SparseMatrix.from_dense(operands[0.9], formats=("ell", "csr"),
                                block=BLOCK)

    @jax.jit
    def gstep(vals, hh):
        return jax.grad(
            lambda v, x: jnp.sum(matmul(A.with_data(v), x) ** 2),
            argnums=(0, 1))(vals, hh)

    gv, gh = gstep(A.data, h)
    assert gv.shape == A.data.shape and gh.shape == h.shape
    assert np.isfinite(np.asarray(gv)).all()
    assert np.isfinite(np.asarray(gh)).all()
    # second call reuses the trace (plan memoized; nothing re-planned)
    gv2, _ = gstep(A.data, h)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gv2))


def test_grad_through_gat_attention(rng):
    """End-to-end: GAT's SDDMM -> softmax -> SpMM chain differentiates
    (its backward mixes both duality rules)."""
    from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
    from repro.data.pipeline import random_graph
    from repro.models.gnn import build_graph, gat_forward, init_gat

    adj = random_graph(48, avg_degree=4, seed=2, clustered=False)
    g = build_graph(adj, GCFG)
    params = init_gat(jax.random.PRNGKey(0), GCFG)
    x = jnp.asarray(rng.normal(size=(48, GCFG.in_features))
                    .astype(np.float32))

    def loss(p):
        return jnp.sum(gat_forward(p, g, x) ** 2)

    grads = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(l)).all() for l in flat)
    assert any(float(jnp.abs(l).sum()) > 0 for l in flat)


def test_sell_kernel_route_grads_match_dense(operands, h):
    """The tile-pruned Pallas route (interpret mode) differentiates to
    the same gradients as dense autodiff."""
    dense = operands[0.99]
    A = SparseMatrix.from_dense(dense, format="sell", block=BLOCK)
    w = jnp.asarray(np.linspace(-1, 1, D, dtype=np.float32))

    def sparse_loss(vals, hh):
        y = matmul(A.with_data(vals), hh, policy="sell",
                   use_kernel=True, interpret=True)
        return jnp.sum(jnp.tanh(y) * w)

    def dense_loss(ad, hh):
        return jnp.sum(jnp.tanh(ad @ hh) * w)

    gv, gh = jax.grad(sparse_loss, argnums=(0, 1))(A.data, h)
    g_ad, g_hd = jax.grad(dense_loss, argnums=(0, 1))(jnp.asarray(dense), h)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(g_hd),
                               rtol=1e-5, atol=1e-5)
    mask = dense != 0
    g_sparse = A.with_data(gv).to_dense()
    np.testing.assert_allclose(g_sparse[mask], np.asarray(g_ad)[mask],
                               rtol=1e-5, atol=1e-5)
    assert (g_sparse[~mask] == 0).all()


# ---------------------------------------------------------------------------
# SpMV: the d = 1 fast lane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("path,fmt", PATH_FORMATS)
def test_spmv_every_path_matches_dense(operands, sparsity, path, fmt):
    dense = operands[sparsity]
    A = SparseMatrix.from_dense(dense, format=fmt, block=BLOCK)
    v = np.linspace(-1, 1, N, dtype=np.float32)
    y = spmv(A, v, policy=path)
    assert y.shape == (N,)
    np.testing.assert_allclose(np.asarray(y), dense @ v,
                               rtol=2e-4, atol=2e-4)
    # transpose rides the same lane (auto policy: the transposed carrier
    # may expose a different path set, e.g. sell.T falls back to csr)
    np.testing.assert_allclose(np.asarray(spmv(A.T, v)),
                               dense.T @ v, rtol=2e-4, atol=2e-4)


def test_matmul_1d_delegates_to_spmv_op(operands):
    """``A @ v`` plans on the dedicated unit-width surface — the plan
    is tagged ``spmv``, not an ``spmm`` with d = 1."""
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, format="ell", block=BLOCK)
    v = np.ones(N, np.float32)
    clear_log()
    A @ v
    ops = [p.op for p in dispatch_log()]
    assert "spmv" in ops and "spmm" not in ops
    assert last_plan().op == "spmv"
    # the 2-D product still plans as spmm
    clear_log()
    A @ np.ones((N, D), np.float32)
    assert last_plan().op == "spmm"


def test_spmv_rejects_matrix_rhs_and_unavailable_path(operands):
    A = SparseMatrix.from_dense(operands[0.9], format="csr")
    with pytest.raises(ValueError, match="rows but A has"):
        spmv(A, np.ones(N - 4, np.float32))
    with pytest.raises(ValueError, match="not among available paths"):
        spmv(A, np.ones(N, np.float32), policy="ell")


@pytest.mark.parametrize("path,fmt", PATH_FORMATS)
def test_spmv_grads_match_dense_autodiff(operands, path, fmt):
    dense = operands[0.9]
    A = SparseMatrix.from_dense(dense, format=fmt, block=BLOCK)
    v = jnp.asarray(np.linspace(-1, 1, N, dtype=np.float32))
    w = jnp.asarray(np.linspace(1, 2, N, dtype=np.float32))

    def sparse_loss(vals, x):
        return jnp.sum(jnp.tanh(spmv(A.with_data(vals), x,
                                     policy=path)) * w)

    def dense_loss(ad, x):
        return jnp.sum(jnp.tanh(ad @ x) * w)

    gv, gx = jax.grad(sparse_loss, argnums=(0, 1))(A.data, v)
    g_ad, g_xd = jax.grad(dense_loss, argnums=(0, 1))(jnp.asarray(dense), v)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(g_xd),
                               rtol=1e-5, atol=1e-5)
    mask = dense != 0
    g_sparse = A.with_data(gv).to_dense()
    np.testing.assert_allclose(g_sparse[mask], np.asarray(g_ad)[mask],
                               rtol=1e-5, atol=1e-5)
    assert (g_sparse[~mask] == 0).all(), "gradient resurrected a zero"


def test_spmv_jit_matches_eager(operands):
    dense = operands[0.99]
    A = SparseMatrix.from_dense(dense, formats=("sell", "csr"),
                                block=BLOCK)
    v = jnp.asarray(np.linspace(-1, 1, N, dtype=np.float32))
    eager = spmv(A, v)
    jitted = jax.jit(lambda a, x: spmv(a, x))(A, v)
    np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(eager), dense @ np.asarray(v),
                               rtol=2e-4, atol=2e-4)
