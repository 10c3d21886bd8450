"""The sell path is priced by the executor that runs it.

The jnp reference works slot by slot; the Pallas kernels walk the live
(bm x bn) tiles of the SELL-C-σ packing and multiply each densely.  On a
skewed graph's hyper-sparse adjacency the tiles hold a few slots each,
so the kernels' work is three orders of magnitude above the slot count
and auto must route around them.  Pure planning from coordinates:
nothing here runs a kernel.
"""
import numpy as np
import pytest

from repro.core.formats import SellCS
from repro.dispatch import (DEFAULT_COST_MODEL, MatrixStats,
                            plan_fused_attention, plan_sddmm, plan_spmm)
from repro.dispatch.dispatcher import plan_spmv

BLOCK = (64, 64)
ALL_PATHS = ("ell", "sell", "csr", "dense")


def _skewed_coords(n, avg_degree, rng):
    """Pareto(2)-skewed rows, uniform columns (``random_graph``'s draws),
    with the self-loops GCN normalisation adds."""
    w = rng.pareto(2.0, n) + 1.0
    w /= w.sum()
    draws = int(avg_degree * n)
    rows = rng.choice(n, size=draws, p=w)
    cols = rng.integers(0, n, size=draws)
    loops = np.arange(n, dtype=np.int64) * (n + 1)
    key = np.unique(np.concatenate([rows.astype(np.int64) * n + cols,
                                    loops]))
    return key // n, key % n


def _uniform_coords(n, sparsity, rng):
    rows, cols = np.nonzero(rng.random((n, n)) < (1.0 - sparsity))
    return rows, cols


def _banded_coords(n, half_width):
    i = np.repeat(np.arange(n), 2 * half_width + 1)
    j = i + np.tile(np.arange(-half_width, half_width + 1), n)
    keep = (j >= 0) & (j < n)
    return i[keep], j[keep]


def _coords(kind):
    rng = np.random.default_rng(17)
    if kind == "skewed":
        return 2048, _skewed_coords(2048, 8, rng)
    if kind == "banded":
        return 1024, _banded_coords(1024, 5)
    sparsity = {"uniform90": 0.9, "uniform999": 0.999}[kind]
    return 1024, _uniform_coords(1024, sparsity, rng)


def _stats(kind):
    n, (rows, cols) = _coords(kind)
    return MatrixStats.from_coords((n, n), rows, cols, *BLOCK)


@pytest.fixture(scope="module")
def skewed_stats():
    return _stats("skewed")


# ---------------------------------------------------------------------------
# (a) the stats count the tiles the packer makes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["skewed", "banded", "uniform90",
                                  "uniform999"])
def test_sell_live_tiles_match_the_packing(kind):
    n, (rows, cols) = _coords(kind)
    dense = np.zeros((n, n), np.float32)
    dense[rows, cols] = 1.0
    sell = SellCS.from_dense(dense, block=BLOCK)
    stats = MatrixStats.from_coords((n, n), rows, cols, *BLOCK)
    assert stats.sell_live_tiles == sell.n_tiles > 0
    assert stats.sell_tile_elements == sell.n_tiles * BLOCK[0] * BLOCK[1]
    assert stats.sell_stored_elements == sell.n_slots


def test_unmeasured_tile_count_prices_at_its_upper_bound(skewed_stats):
    """A capacity overlay cannot know which tiles inserts open: its
    count drops to the bound, at least the exact count."""
    cap = skewed_stats.with_capacity(skewed_stats.nnz + 512)
    assert cap.sell_live_tiles == 0
    nbc = cap.shape[1] // cap.block_n
    bound = min(cap.sell_stored_elements, cap.n_block_rows * nbc)
    assert cap.sell_tile_elements == bound * cap.block_m * cap.block_n
    assert cap.sell_tile_elements >= skewed_stats.sell_tile_elements


def test_block_diag_stats_sum_the_parts_tiles(rng):
    from repro.batch import BatchedSparseMatrix
    from repro.sparse import SparseMatrix

    mats = []
    for n, s in ((40, 0.97), (64, 0.99), (24, 0.9)):
        dense = (rng.random((n, n)) < (1 - s)).astype(np.float32)
        mats.append(SparseMatrix.from_dense(
            dense, formats=("sell", "csr"), block=(8, 8)))
    B = BatchedSparseMatrix.from_matrices(mats)
    assert B.stats.sell_live_tiles == B.matrix.form("sell").n_tiles \
        == sum(m.stats.sell_live_tiles for m in mats)


# ---------------------------------------------------------------------------
# (b) the kernel's price sends the skewed graph to csr; the reference's
#     keeps sell
# ---------------------------------------------------------------------------


def _plan(op, stats, use_kernel):
    kw = dict(use_kernel=use_kernel, candidates=ALL_PATHS,
              cost_model=DEFAULT_COST_MODEL)
    if op == "spmm":
        return plan_spmm(stats, 128, **kw)
    if op == "sddmm":
        return plan_sddmm(stats, 2, **kw)
    return plan_fused_attention(stats, 2, 128, **kw)


@pytest.mark.parametrize("op", ["spmm", "sddmm", "fused_attn"])
@pytest.mark.parametrize("use_kernel,expected", [(True, "csr"),
                                                 (False, "sell")])
def test_skewed_graph_sell_price_follows_the_executor(skewed_stats, op,
                                                      use_kernel, expected):
    plan = _plan(op, skewed_stats, use_kernel)
    assert plan.path == expected, plan.describe()
    s = skewed_stats
    tile_fill = s.nnz / s.sell_tile_elements
    assert tile_fill < 0.01  # the hyper-sparse tiles this guards
    basis = (f"({s.sell_live_tiles} tiles)" if use_kernel
             else f"({s.sell_stored_elements} slots)")
    assert basis in plan.reason


def test_interpret_mode_prices_the_kernel(skewed_stats):
    plan = plan_spmm(skewed_stats, 128, use_kernel=False, interpret=True,
                     candidates=ALL_PATHS, cost_model=DEFAULT_COST_MODEL)
    assert plan.path == "csr", plan.describe()


def test_spmv_sell_lane_is_priced_by_slots(skewed_stats):
    """SpMV's sell executor reduces slot by slot on every backend."""
    plan = plan_spmv(skewed_stats, use_kernel=True, candidates=ALL_PATHS,
                     cost_model=DEFAULT_COST_MODEL)
    ref = DEFAULT_COST_MODEL.spmm_costs(skewed_stats, 1)
    assert plan.costs["sell"] == pytest.approx(ref["sell"])
    assert "slots)" in plan.reason


# ---------------------------------------------------------------------------
# (c) where tiles are well filled the tiled paths still win
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", [(2, 2), (4, 4)])
def test_uniform_90_plans_a_tiled_path_on_the_kernel(block):
    rng = np.random.default_rng(23)
    rows, cols = _uniform_coords(512, 0.9, rng)
    stats = MatrixStats.from_coords((512, 512), rows, cols, *block)
    plan = plan_spmm(stats, 64, use_kernel=True, candidates=ALL_PATHS,
                     cost_model=DEFAULT_COST_MODEL)
    assert plan.path in ("ell", "sell"), plan.describe()


def test_from_dense_auto_packs_what_the_kernel_backend_would_plan(
        monkeypatch):
    """format="auto" applies the same rule: on a kernel backend the
    skewed graph packs csr, on the reference it packs sell."""
    from repro.dispatch import dispatcher
    from repro.sparse import SparseMatrix

    n, (rows, cols) = _coords("skewed")
    dense = np.zeros((n, n), np.float32)
    dense[rows, cols] = 1.0
    assert SparseMatrix.from_dense(dense).formats == ("sell",)
    monkeypatch.setattr(dispatcher, "default_use_kernel",
                        lambda config=None: True)
    assert SparseMatrix.from_dense(dense).formats == ("csr",)


# ---------------------------------------------------------------------------
# (d) the TPU constants pick the chip's fastest path at every probe shape
# ---------------------------------------------------------------------------

PROBE_N, PROBE_D = 4096, 128


def _probe_stats(shape_name):
    """Stats of one probe matrix, from its coordinates: uniform 4096² at a
    sparsity (seeds as the probe drew them), or the cells' pl16k graph
    (16,384 nodes, Pareto(2) rows, 8 draws a node, graph seed 0)."""
    if shape_name == "pl16k":
        tag = int.from_bytes(b"graph", "little") % (2 ** 63)
        n, (rows, cols) = 16384, _skewed_coords(
            16384, 8, np.random.default_rng([0, tag]))
    else:
        sparsity = float(shape_name)
        seed = 100 + (0.9, 0.95, 0.99, 0.999).index(sparsity)
        n = PROBE_N
        rows, cols = _uniform_coords(n, sparsity,
                                     np.random.default_rng(seed))
    return MatrixStats.from_coords((n, n), rows, cols, *BLOCK)


# Each probe shape's fastest path on one TPU v5e (d = 128, 64x64 blocks;
# ell, sell kernel + tile view, csr and dense each timed jitted) and the
# candidates the probe offered: the graph carries ell, sell and csr.
PROBE_FASTEST = {
    "0.9": ("ell", ALL_PATHS),
    "0.95": ("ell", ALL_PATHS),
    "0.99": ("ell", ALL_PATHS),
    "0.999": ("csr", ALL_PATHS),
    "pl16k": ("csr", ("ell", "sell", "csr")),
}


@pytest.mark.parametrize("shape_name", sorted(PROBE_FASTEST))
def test_tpu_constants_pick_the_chips_fastest_path(shape_name):
    from repro.dispatch.cost_model import DEVICE_COST_MODELS

    fastest, cand = PROBE_FASTEST[shape_name]
    stats = _probe_stats(shape_name)
    plan = plan_spmm(stats, PROBE_D, use_kernel=True, candidates=cand,
                     cost_model=DEVICE_COST_MODELS["TPU v5 lite"])
    assert plan.path == fastest, plan.describe()


def test_probe_graph_is_the_cells_graph():
    """The pl16k probe stats are those of the training cells' adjacency
    (16,384 nodes with self-loops): 147,375 entries in 49,991 tiles."""
    stats = _probe_stats("pl16k")
    assert (stats.nnz, stats.sell_live_tiles,
            stats.sell_stored_elements) == (147375, 49991, 174528)


def test_default_cost_model_follows_the_device_kind(monkeypatch):
    """Planning with no explicit model prices with the running device's
    fitted constants, and with the shipped ones on any other device."""
    import jax

    from repro.dispatch import dispatcher
    from repro.dispatch.cost_model import DEVICE_COST_MODELS

    assert dispatcher.default_cost_model() is DEFAULT_COST_MODEL

    class _Device:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Device()])
    assert dispatcher.default_cost_model() is \
        DEVICE_COST_MODELS["TPU v5 lite"]
    stats = _probe_stats("0.99")
    plan = plan_spmm(stats, PROBE_D, use_kernel=True, candidates=ALL_PATHS)
    assert plan.costs == plan_spmm(
        stats, PROBE_D, use_kernel=True, candidates=ALL_PATHS,
        cost_model=DEVICE_COST_MODELS["TPU v5 lite"]).costs
