"""Dynamic-graph delta updates: ``repro.serve.runtime.DeltaGraph``.

Covers the acceptance contract of the overlay:
  * **exact parity** — any interleaved sequence of edge inserts,
    updates, and deletes produces the same SpMM (and SDDMM) results as
    a from-scratch rebuild of the final graph, within 1e-6, on the csr
    and sell overlays at 0.9/0.99 sparsity;
  * **retrace stability** — a jitted consumer traces exactly once
    across >= 1000 mixed deltas (capacity stats + constant array
    shapes), with zero repacks in between;
  * slack exhaustion triggers an automatic repack around the pending
    edge (consumers retrace once, parity holds);
  * tombstoned slots contribute exactly zero (delete-all == zero
    output);
  * delta application invalidates exact stats (the planner's repack
    signal) while the served capacity stats stay constant;
  * the background repack overlaps serving and replays the delta
    journal on swap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dispatch.stats import MatrixStats
from repro.serve.runtime import DeltaGraph
from repro.sparse import SparseMatrix, matmul, sddmm

BLOCK = (8, 8)
N = 64
D = 8
SWEEP = [0.9, 0.99]


def _dense(rng, n=N, sparsity=0.9):
    a = np.where(rng.random((n, n)) < (1.0 - sparsity),
                 rng.normal(size=(n, n)), 0.0).astype(np.float32)
    if not a.any():
        a[0, 0] = 1.0
    return a


def _make(rng, form, sparsity, **kw):
    dense = _dense(rng, sparsity=sparsity)
    kw.setdefault("block", BLOCK)
    if form == "sell":
        kw.setdefault("c", 16)
    return dense, DeltaGraph(dense, form=form, **kw)


def _random_deltas(rng, dg, dense, n_deltas):
    """Apply a mixed insert/update/delete stream; return the live dense."""
    live = {(int(r), int(c)): float(dense[r, c])
            for r, c in zip(*np.nonzero(dense))}
    for _ in range(n_deltas):
        op = rng.random()
        if op < 0.4 and len(live) > 1:            # delete an existing edge
            r, c = list(live)[rng.integers(len(live))]
            dg.delete(r, c)
            del live[(r, c)]
        elif op < 0.7:                            # update in place
            r, c = list(live)[rng.integers(len(live))]
            v = float(rng.normal())
            while v == 0.0:
                v = float(rng.normal())
            dg.insert(r, c, v)
            live[(r, c)] = v
        else:                                     # insert a fresh edge
            r, c = int(rng.integers(N)), int(rng.integers(N))
            v = float(rng.normal())
            while v == 0.0 or (r, c) in live:
                r, c = int(rng.integers(N)), int(rng.integers(N))
                v = float(rng.normal())
            dg.insert(r, c, v)
            live[(r, c)] = v
    out = np.zeros((N, N), np.float32)
    for (r, c), v in live.items():
        out[r, c] = v
    return out


# ---------------------------------------------------------------------------
# parity: deltas == from-scratch rebuild
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("form", ["csr", "sell"])
def test_delta_sequence_matches_rebuild(rng, form, sparsity):
    dense, dg = _make(rng, form, sparsity)
    final = _random_deltas(rng, dg, dense, 120)
    np.testing.assert_allclose(np.asarray(dg.matrix.densify()), final,
                               rtol=0, atol=0)
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    rebuild = SparseMatrix.from_dense(final, formats=(form,), block=BLOCK)
    got = matmul(dg.matrix, h, policy=form)
    want = matmul(rebuild, h, policy=form)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert dg.live_nnz == int((final != 0).sum())


@pytest.mark.parametrize("sparsity", SWEEP)
@pytest.mark.parametrize("form", ["csr", "sell"])
def test_delta_sddmm_matches_rebuild(rng, form, sparsity):
    dense, dg = _make(rng, form, sparsity)
    final = _random_deltas(rng, dg, dense, 80)
    b = jnp.asarray(rng.normal(size=(N, 4)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(4, N)).astype(np.float32))
    rebuild = SparseMatrix.from_dense(final, formats=(form,), block=BLOCK)
    got = sddmm(dg.matrix, b, c, policy=form).densify()
    want = sddmm(rebuild, b, c, policy=form).densify()
    # tombstones sample to exactly zero — parity is dense-wide
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_delete_all_is_zero(rng):
    dense, dg = _make(rng, "csr", 0.99)
    for r, c in zip(*np.nonzero(dense)):
        dg.delete(int(r), int(c))
    assert dg.live_nnz == 0
    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(matmul(dg.matrix, h, policy="csr")), np.zeros((N, D)))


# ---------------------------------------------------------------------------
# retrace stability
# ---------------------------------------------------------------------------


def test_thousand_deltas_zero_retrace(rng):
    dense, dg = _make(rng, "csr", 0.9, slack=4.0)
    traces = []

    @jax.jit
    def consume(m, h):
        traces.append(1)  # runs at trace time only
        return m @ h

    h = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    consume(dg.matrix, h)
    final = dense
    for _ in range(10):
        final = _random_deltas(rng, dg, final, 110)
        consume(dg.matrix, h)
    assert dg.deltas_applied >= 1000
    assert dg.repacks == 0
    assert len(traces) == 1  # capacity stats + fixed shapes: no retrace
    np.testing.assert_allclose(np.asarray(consume(dg.matrix, h)),
                               final.astype(np.float64) @ np.asarray(h),
                               rtol=1e-4, atol=1e-4)


def test_sell_value_churn_zero_repack(rng):
    dense, dg = _make(rng, "sell", 0.9)
    edges = list(zip(*np.nonzero(dense)))
    for i in range(300):
        r, c = edges[i % len(edges)]
        dg.delete(int(r), int(c))
        dg.insert(int(r), int(c), float(i + 1))
    assert dg.repacks == 0
    assert dg.deltas_applied == 600


def test_slack_exhaustion_auto_repacks(rng):
    dense, dg = _make(rng, "csr", 0.99, slack=0.0)
    free0 = dg.free_slots()
    k = 0
    while dg.repacks == 0:  # keep inserting until the pool runs dry
        r, c = divmod(k, N)
        if dense[r, c] == 0:
            dg.insert(r, c, 1.0)
            dense[r, c] = 1.0
        k += 1
        assert k < N * N, "slack never exhausted"
    assert dg.repacks == 1 and dg.free_slots() > 0
    # the edge that overflowed the pool is live after the repack
    np.testing.assert_allclose(np.asarray(dg.matrix.densify()), dense)
    assert dg.capacity >= free0


def test_sell_out_of_structure_insert_repacks(rng):
    dense, dg = _make(rng, "sell", 0.9, width_slack=1)
    # overflow one row's slack: insert into fresh columns until repack
    r = int(np.argmax((dense != 0).sum(axis=1)))
    # the width ladder quantizes slice widths up, so the row starts with
    # some headroom beyond width_slack — keep inserting until it runs out
    empty_cols = np.flatnonzero(dense[r] == 0)
    for j, c in enumerate(empty_cols):
        dg.insert(r, int(c), float(j + 1))
        dense[r, c] = float(j + 1)
        if dg.repacks:
            break
    assert dg.repacks >= 1
    np.testing.assert_allclose(np.asarray(dg.matrix.densify()), dense)


# ---------------------------------------------------------------------------
# stats plumbing
# ---------------------------------------------------------------------------


def test_capacity_stats_constant_exact_stats_track(rng):
    dense, dg = _make(rng, "csr", 0.9)
    served0 = dg.matrix.stats
    assert served0.nnz == dg.capacity  # priced at capacity, not live
    r, c = next(zip(*np.nonzero(dense)))
    dg.delete(int(r), int(c))
    assert dg.stats_invalidations == 1
    assert dg.matrix.stats == served0          # served aux unchanged
    assert dg.exact_stats.nnz == dg.live_nnz   # true structure tracks
    dg.repack()
    assert dg.matrix.stats != served0          # repack re-prices


def test_with_capacity_validates():
    s = MatrixStats.from_coords((8, 8), np.arange(4), np.arange(4))
    assert s.with_capacity(10).nnz == 10
    with pytest.raises(ValueError):
        s.with_capacity(2)


def test_insert_zero_and_missing_delete_raise(rng):
    dense, dg = _make(rng, "csr", 0.9)
    with pytest.raises(ValueError):
        dg.insert(0, 0, 0.0)
    r, c = np.nonzero(dense == 0)
    with pytest.raises(KeyError):
        dg.delete(int(r[0]), int(c[0]))


# ---------------------------------------------------------------------------
# background repack
# ---------------------------------------------------------------------------


def test_background_repack_swaps_and_replays(rng):
    dense, dg = _make(rng, "csr", 0.9, slack=0.5)
    final = _random_deltas(rng, dg, dense, 60)
    assert dg.maybe_repack_async(low_water=1.0)  # force a rebuild start
    # deltas during the rebuild land in the journal and replay on swap
    r, c = next(zip(*np.nonzero(final)))
    dg.delete(int(r), int(c))
    final[r, c] = 0
    assert dg.poll_repack(timeout=30.0)
    assert dg.repacks == 1
    np.testing.assert_allclose(np.asarray(dg.matrix.densify()), final)
    assert dg.matrix.stats.nnz == dg.capacity
