"""Sparsity-adaptive dispatch layer: path agreement + the paper's crossover.

The sweep asserts two things the paper measures:
  (a) every execution path computes the same product (dense oracle,
      Pallas kernel validated in interpret mode), and
  (b) the cost model reproduces the crossover — the Block-ELL streaming
      path at 90% sparsity, the element-level CSR path at >=99%.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import BlockCOO, BlockELL
from repro.dispatch import (AutotuneCache, CostModel, MatrixStats,
                            clear_log, dispatch_log, last_plan,
                            normalize_policy, plan_spmm, sparsity_bucket)
from repro.dispatch.autotune import make_key
from repro.sparse import SparseMatrix
from repro.sparse.ops import matmul, sddmm

SWEEP = [0.5, 0.9, 0.99, 0.999]
N, D = 512, 64
BLOCK = 4  # small blocks keep block-granularity honest at uniform sparsity
# the original three paths' forms: no sell packing carried
FORMS = ("ell", "csr")


def _uniform_sparse(rng, n, sparsity):
    mask = rng.random((n, n)) < (1.0 - sparsity)
    return np.where(mask, rng.normal(size=(n, n)), 0.0).astype(np.float32)


@pytest.fixture(scope="module")
def sweep_operands():
    rng = np.random.default_rng(42)
    out = {}
    for s in SWEEP:
        dense = _uniform_sparse(rng, N, s)
        out[s] = (dense, SparseMatrix.from_dense(
            dense, formats=FORMS, block=(BLOCK, BLOCK)))
    return out


# ---------------------------------------------------------------------------
# (a) all paths agree with the dense oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("path", ["ell", "csr", "dense"])
def test_spmm_paths_match_dense_oracle(sweep_operands, path, sparsity):
    dense, op = sweep_operands[sparsity]
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    y = matmul(op, h, policy=path)
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)
    assert last_plan("spmm").path == path


@pytest.mark.parametrize("sparsity", [0.9, 0.999])
def test_spmm_kernel_path_interpret_matches_oracle(sparsity):
    """The Pallas kernel route through the dispatcher (interpret mode)."""
    rng = np.random.default_rng(3)
    dense = _uniform_sparse(rng, 256, sparsity)
    ell = BlockELL.from_dense(dense, 64, 128)
    h = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    y = matmul(SparseMatrix.from_blockell(ell), h, policy="ell",
               use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=5e-4, atol=5e-4)
    plan = last_plan("spmm")
    assert plan.path == "ell" and plan.interpret


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("path", ["ell", "csr", "dense"])
def test_sddmm_paths_match_dense_oracle(path, sparsity):
    rng = np.random.default_rng(11)
    n, k = 256, 2
    mask = (rng.random((n, n)) < (1.0 - sparsity)).astype(np.float32)
    a = SparseMatrix.from_blockcoo(BlockCOO.from_dense(mask, 16, 16)) \
        .with_form("csr")
    b = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    out = sddmm(a, b, c, policy=path)
    oracle = mask * (np.asarray(b) @ np.asarray(c))
    np.testing.assert_allclose(out.to_dense(), oracle,
                               rtol=2e-4, atol=2e-4)
    assert last_plan("sddmm").path == path


def test_sddmm_kernel_path_interpret_matches_oracle():
    rng = np.random.default_rng(5)
    n, k = 256, 128
    mask = (rng.random((n, n)) < 0.1).astype(np.float32)
    a = SparseMatrix.from_blockcoo(BlockCOO.from_dense(mask, 64, 64))
    b = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    out = sddmm(a, b, c, policy="ell", use_kernel=True, interpret=True)
    oracle = mask * (np.asarray(b) @ np.asarray(c))
    np.testing.assert_allclose(out.to_dense(), oracle,
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# (b) the crossover: ELL at 90% sparsity, CSR at >=99% (the original
#     three paths), SELL taking over the hyper-sparse side when its form
#     is carried
# ---------------------------------------------------------------------------


# among the original three paths (no sell packing carried)
EXPECTED_PATH = {0.5: "dense", 0.9: "ell", 0.99: "csr", 0.999: "csr"}
# with every path priceable, SELL-C-σ owns the hyper-sparse side
EXPECTED_PATH_FULL = {0.5: "dense", 0.9: "ell", 0.99: "sell",
                      0.999: "sell"}


@pytest.mark.parametrize("sparsity", SWEEP)
def test_cost_model_reproduces_paper_crossover(sweep_operands, sparsity):
    """The paper's crossover among the three original paths is intact;
    unrestricted, the sell path replaces csr past the padding cliff."""
    _, op = sweep_operands[sparsity]
    three = plan_spmm(op.stats, D, policy="auto",
                      candidates=("ell", "csr", "dense"))
    assert three.path == EXPECTED_PATH[sparsity], three.describe()
    full = plan_spmm(op.stats, D, policy="auto")
    assert full.path == EXPECTED_PATH_FULL[sparsity], full.describe()


@pytest.mark.parametrize("sparsity", SWEEP)
def test_spmm_auto_dispatch_switches_paths(sweep_operands, sparsity):
    """matmul(..., policy="auto") routes ELL at 90%, CSR at >=99%."""
    dense, op = sweep_operands[sparsity]
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    y = matmul(op, h, policy="auto")
    plan = last_plan("spmm")
    assert plan.path == EXPECTED_PATH[sparsity], plan.describe()
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)


def test_sddmm_auto_dispatch_crossover():
    rng = np.random.default_rng(13)
    n, k = 256, 2
    b = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    for sparsity, expected in ((0.9, "ell"), (0.999, "csr")):
        mask = (rng.random((n, n)) < (1.0 - sparsity)).astype(np.float32)
        a = SparseMatrix.from_blockcoo(BlockCOO.from_dense(mask, 4, 4)) \
            .with_form("csr")
        sddmm(a, b, c, policy="auto")
        plan = last_plan("sddmm")
        assert plan.path == expected, plan.describe()


def test_padded_stream_blowup_drives_the_crossover(sweep_operands):
    """The mechanism, not just the outcome: the blow-up is monotone in
    sparsity and crosses c_csr/c_ell between 0.9 and 0.99."""
    cm = CostModel()
    blowups = [sweep_operands[s][1].stats.padded_stream_blowup
               for s in SWEEP]
    assert blowups == sorted(blowups)
    ratio = cm.c_csr / cm.c_ell
    assert blowups[SWEEP.index(0.9)] < ratio < blowups[SWEEP.index(0.99)]


# ---------------------------------------------------------------------------
# the sell path at extreme sparsity (the tentpole crossover)
# ---------------------------------------------------------------------------


def _sell_capable(dense):
    return SparseMatrix.from_dense(dense, formats=("ell", "csr", "sell"),
                                   block=(BLOCK, BLOCK))


@pytest.mark.parametrize("sparsity,expected", [
    (0.9, "ell"),       # moderate sparsity: blocked streaming still wins
    (0.995, "sell"),    # past the padding cliff: sell takes over
    (0.999, "sell"),
])
def test_auto_routes_sell_past_the_cliff(sparsity, expected):
    """policy=auto picks sell at >=99.5% sparsity, ell at 90%."""
    rng = np.random.default_rng(51)
    dense = _uniform_sparse(rng, N, sparsity)
    op = _sell_capable(dense)
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    clear_log()
    y = matmul(op, h, policy="auto")
    plan = last_plan("spmm")
    assert plan.path == expected, plan.describe()
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)
    # the dispatch log records the decision AND the predicted costs
    logged = [p for p in dispatch_log() if p.op == "spmm"]
    assert logged and logged[-1].path == expected
    assert logged[-1].costs is not None
    assert set(logged[-1].costs) == {"ell", "sell", "csr", "dense"}
    assert logged[-1].costs[expected] == min(logged[-1].costs.values())
    assert "cost model" in logged[-1].reason


@pytest.mark.parametrize("sparsity", [0.9, 0.995])
def test_sell_dispatch_log_records_predicted_cost_sddmm(sparsity):
    rng = np.random.default_rng(53)
    mask = (rng.random((N, N)) < (1.0 - sparsity)).astype(np.float32)
    op = SparseMatrix.from_dense(mask, formats=("coo", "csr", "sell"),
                                 block=(BLOCK, BLOCK))
    b = jnp.asarray(rng.normal(size=(N, 2)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(2, N)).astype(np.float32))
    sddmm(op, b, c, policy="auto")
    plan = last_plan("sddmm")
    assert plan.costs is not None and "sell" in plan.costs
    if sparsity >= 0.995:
        assert plan.path == "sell", plan.describe()


def test_sell_not_a_candidate_without_the_form():
    """A matrix that never packed sell cannot be routed to it."""
    rng = np.random.default_rng(57)
    dense = _uniform_sparse(rng, 128, 0.999)
    op = SparseMatrix.from_dense(dense, formats=("ell", "csr"),
                                 block=(BLOCK, BLOCK))
    h = jnp.asarray(rng.normal(size=(128, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="not among available paths"):
        matmul(op, h, policy="sell")
    matmul(op, h, policy="auto")
    assert last_plan("spmm").path in ("ell", "csr", "dense")


def test_with_form_makes_sell_routable():
    """Lazy conversion: adding the sell form turns the path on."""
    rng = np.random.default_rng(59)
    dense = _uniform_sparse(rng, 256, 0.995)
    op = SparseMatrix.from_dense(dense, formats=("ell", "csr"),
                                 block=(BLOCK, BLOCK))
    both = op.with_form("sell")
    assert both.formats == ("ell", "csr", "sell")
    assert op.with_form("ell") is op  # no-op when already carried
    h = jnp.asarray(rng.normal(size=(256, D)).astype(np.float32))
    matmul(both, h, policy="auto")
    assert last_plan("spmm").path == "sell"


# ---------------------------------------------------------------------------
# policy plumbing
# ---------------------------------------------------------------------------


def test_policy_normalization_and_errors():
    assert normalize_policy("BLOCK") == "ell"
    assert normalize_policy("coo") == "csr"
    assert normalize_policy("auto") == "auto"
    with pytest.raises(ValueError):
        normalize_policy("fastest")


def test_forced_policy_outside_candidates_raises(sweep_operands):
    _, op = sweep_operands[0.9]
    with pytest.raises(ValueError):
        plan_spmm(op.stats, D, policy="dense", candidates=("ell", "csr"))


def test_forced_ell_use_kernel_false_runs_reference(sweep_operands):
    """An explicit use_kernel=False runs the forced blocked path on its
    jnp reference, and the logged plan says so."""
    dense, op = sweep_operands[0.999]  # auto would pick csr here
    rng = np.random.default_rng(17)
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    y = matmul(op, h, policy="ell", use_kernel=False)
    plan = last_plan("spmm")
    assert plan.path == "ell" and plan.use_kernel is False
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)


def test_matmul_accepts_blockell_and_dense():
    rng = np.random.default_rng(19)
    dense = _uniform_sparse(rng, 128, 0.9)
    h = jnp.asarray(rng.normal(size=(128, 32)).astype(np.float32))
    ell = SparseMatrix.from_blockell(BlockELL.from_dense(dense, 16, 16))
    y1 = matmul(ell, h, policy="ell")
    y2 = matmul(SparseMatrix.from_dense(dense, formats=("csr",)), h,
                policy="csr")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("path", ["ell", "csr", "dense", "auto"])
def test_spmm_mismatched_h_rows_raises(path):
    """H with the wrong row count must raise, not silently pad/truncate."""
    with pytest.raises(ValueError, match="60 rows but A has 64"):
        matmul(SparseMatrix.from_dense(np.eye(64, dtype=np.float32),
                                       formats=FORMS),
               jnp.ones((60, 4)), policy=path)


def test_spmm_non_divisible_shapes_trim_correctly():
    """Dense operand whose shape is not a block multiple: ell path pads
    internally and the output is trimmed back to the logical shape."""
    rng = np.random.default_rng(23)
    m, n, d = 100, 70, 16
    mask = rng.random((m, n)) < 0.1
    dense = np.where(mask, rng.normal(size=(m, n)), 0.0).astype(np.float32)
    op = SparseMatrix.from_dense(dense, formats=FORMS, block=(16, 16))
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    for path in ("ell", "csr", "dense"):
        y = matmul(op, h, policy=path)
        assert y.shape == (m, d)
        np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("path", ["ell", "csr", "dense", "auto"])
def test_sddmm_non_divisible_shapes_all_paths(path):
    """A 100x100 mask block-pads to 128x128; B/C are padded to match."""
    rng = np.random.default_rng(31)
    mask = (rng.random((100, 100)) < 0.5).astype(np.float32)
    b = rng.normal(size=(100, 2)).astype(np.float32)
    c = rng.normal(size=(2, 100)).astype(np.float32)
    a = SparseMatrix.from_dense(mask, formats=("coo", "csr"))
    out = sddmm(a, jnp.asarray(b), jnp.asarray(c), policy=path)
    np.testing.assert_allclose(out.to_dense(),
                               mask * (b @ c), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("path", ["ell", "csr", "dense", "auto"])
def test_spmm_1d_h_all_paths(path):
    """A 1-D H takes the SpMV lane: one plan with op "spmv"."""
    rng = np.random.default_rng(37)
    dense = np.where(rng.random((64, 64)) < 0.1, 1.0, 0.0) \
        .astype(np.float32)
    hv = rng.normal(size=64).astype(np.float32)
    op = SparseMatrix.from_dense(dense, formats=FORMS, block=(4, 4))
    y = matmul(op, jnp.asarray(hv), policy=path)
    assert y.shape == (64,)
    plan = last_plan()
    assert plan.op == "spmv", plan.describe()
    if path != "auto":
        assert plan.path == path
    np.testing.assert_allclose(np.asarray(y), dense @ hv,
                               rtol=2e-4, atol=2e-4)


def test_pure_plan_never_claims_autotune(sweep_operands):
    """plan_* cannot time candidates, so the plan must not say it did."""
    _, op = sweep_operands[0.9]
    plan = plan_spmm(op.stats, D, policy="autotune")
    assert plan.policy == "auto" and plan.timings_us is None


@pytest.mark.parametrize("path", ["ell", "sell", "csr", "dense"])
def test_sparse_matrix_under_jit_forced_policy_matches_oracle(path):
    """A SparseMatrix crosses jax.jit as a pytree: the forced path plans
    once, at trace time, from the static stats it carries."""
    rng = np.random.default_rng(41)
    dense = _uniform_sparse(rng, 64, 0.9)
    a = SparseMatrix.from_dense(dense, formats=("ell", "csr", "sell"),
                                block=(16, 16))
    f = jax.jit(lambda m, hh: matmul(m, hh, policy=path))
    clear_log()
    for seed in (0, 1):
        h = jnp.asarray(np.random.default_rng(seed).normal(size=(64, 8))
                        .astype(np.float32))
        np.testing.assert_allclose(np.asarray(f(a, h)),
                                   dense @ np.asarray(h),
                                   rtol=2e-4, atol=2e-4)
    plans = [p for p in dispatch_log() if p.op == "spmm"]
    assert len(plans) == 1  # traced once, not planned per call
    assert plans[0].path == path and plans[0].stats == a.stats


def test_graph_without_stats_raises_clearly():
    from repro.models.gnn import Graph, graph_spmm
    from repro.sparse import SparseMatrix

    rng = np.random.default_rng(43)
    dense = _uniform_sparse(rng, 32, 0.9)
    ell = BlockELL.from_dense(dense, 16, 16)
    # stats-less adjacency (e.g. wrapped from traced arrays): policy
    # routing must fail loudly, not silently pick a path
    adj = SparseMatrix({"ell": ell}, ell.shape, None)
    g = Graph(adj=adj, n_nodes=32)
    with pytest.raises(ValueError, match="build_graph"):
        graph_spmm(g, jnp.ones((32, 4)))


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------


def test_autotune_caches_per_sparsity_bucket(sweep_operands, tmp_path):
    dense, op = sweep_operands[0.99]
    rng = np.random.default_rng(29)
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    cache = AutotuneCache()
    y = matmul(op, h, policy="autotune", autotune_cache=cache)
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(h),
                               rtol=2e-4, atol=2e-4)
    assert len(cache) == 1
    first = last_plan("spmm")
    assert first.timings_us and len(first.timings_us) == 3

    # second matrix in the same bucket (its own plan memo): cache hit,
    # no re-measurement
    misses = cache.misses
    again = SparseMatrix.from_dense(dense, formats=FORMS,
                                    block=(BLOCK, BLOCK))
    matmul(again, h, policy="autotune", autotune_cache=cache)
    assert cache.misses == misses
    assert "cached" in last_plan("spmm").reason

    # persistence round-trip
    p = tmp_path / "autotune.json"
    cache.save(str(p))
    cache2 = AutotuneCache()
    cache2.load(str(p))
    assert len(cache2) == 1
    key = make_key("spmm", op.stats.shape, D, h.dtype, op.stats.density)
    assert cache2.get(key).path == first.path


def test_sparsity_bucket_groups_decades():
    assert sparsity_bucket(0.5) == sparsity_bucket(0.4)
    assert sparsity_bucket(0.1) != sparsity_bucket(0.001)
    # density 0 lands in the hyper-sparse cap bucket
    assert sparsity_bucket(0.0) == sparsity_bucket(1e-12)
    b1, b2 = sparsity_bucket(0.01), sparsity_bucket(0.009)
    assert b1 == b2  # same half-decade


# ---------------------------------------------------------------------------
# consumers: GNN + serving engine
# ---------------------------------------------------------------------------


def test_gcn_policy_paths_agree():
    from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
    from repro.data.pipeline import random_graph
    from repro.models.gnn import build_graph, gcn_forward, init_gcn

    rng = np.random.default_rng(0)
    adj = random_graph(48, avg_degree=4, seed=1, clustered=False)
    g = build_graph(adj, GCFG)
    assert isinstance(g.adj.stats, MatrixStats) and g.adj.stats.nnz > 0
    params = init_gcn(jax.random.PRNGKey(0), GCFG)
    x = jnp.asarray(rng.normal(size=(48, GCFG.in_features))
                    .astype(np.float32))
    outs = {p: np.asarray(gcn_forward(params, g, x, policy=p))
            for p in ("auto", "ell", "csr")}
    np.testing.assert_allclose(outs["ell"], outs["csr"],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(outs["auto"], outs["ell"],
                               rtol=2e-4, atol=2e-4)
    # plans are static metadata: the forward works under jit
    f = jax.jit(lambda p, gg, xx: gcn_forward(p, gg, xx, policy="auto"))
    np.testing.assert_allclose(np.asarray(f(params, g, x)), outs["auto"],
                               rtol=2e-4, atol=2e-4)


def test_gnn_serving_engine_dispatch_report():
    from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
    from repro.data.pipeline import random_graph
    from repro.models.gnn import build_graph, gcn_forward, init_gcn
    from repro.serve.engine import GNNServeConfig, GNNServingEngine

    rng = np.random.default_rng(1)
    adj = random_graph(48, avg_degree=4, seed=2, clustered=False)
    g = build_graph(adj, GCFG)
    params = init_gcn(jax.random.PRNGKey(1), GCFG)
    x = rng.normal(size=(48, GCFG.in_features)).astype(np.float32)

    eng = GNNServingEngine(params, g)
    logits = eng.infer(x)
    assert logits.shape == (48, GCFG.n_classes)
    report = eng.dispatch_report()
    assert report["path"] in ("ell", "csr")
    assert report["n_requests"] == 1
    np.testing.assert_allclose(
        logits, np.asarray(gcn_forward(params, g, jnp.asarray(x),
                                       policy=report["path"])),
        rtol=2e-4, atol=2e-4)

    # forcing the other path still serves correct logits
    other = "csr" if report["path"] == "ell" else "ell"
    eng2 = GNNServingEngine(params, g, GNNServeConfig(policy=other))
    np.testing.assert_allclose(eng2.infer(x), logits, rtol=2e-4, atol=2e-4)
    assert eng2.dispatch_report()["path"] == other


def test_autotune_measure_surfaces_a_raising_candidate():
    """A path that raises (a kernel the compiler refuses) is a fault to
    surface, not an `inf` timing that quietly loses the race."""
    from repro.dispatch.autotune import measure

    def refused():
        raise ValueError("block shape refused by the compiler")

    with pytest.raises(ValueError, match="refused"):
        measure({"ell": refused, "csr": lambda: jnp.ones(4)},
                warmup=0, iters=1)
    m = measure({"ell": lambda: jnp.ones(4), "csr": lambda: jnp.ones(4)},
                warmup=0, iters=1)
    assert m.path in ("ell", "csr") and set(m.timings_us) == {"ell", "csr"}
