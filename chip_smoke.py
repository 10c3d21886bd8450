#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: SparseMatrix -> dispatch -> Pallas
kernels -> GCN/GAT train step -> serving engine, at paper-gnn full width.

    python chip_smoke.py             # one chip, every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded 1.5D / 2D SpMM
                                     # against the one-chip kernel, nothing else

Phases (one chip):
  gcn_train/{ell,sell,auto}  3 SGD steps of the 3-layer GCN (D=256, hidden 128,
                             16 classes) on a 16384-node, degree-8 graph; the
                             step-0 logits against a dense float32 reference
  gat/{ell,sell}             fused GAT forward and parameter gradients against
                             a dense masked-softmax reference
  kernels/{ell,sell}         A @ H, its gradient in A's values, and
                             sample(A, B, C) at N=16384, D=K=256, 90% (ell) and
                             99% (sell) sparsity, against dense
  serve                      ContinuousBatchEngine answers 32 paper-gnn
                             requests on graphs of 256..2048 nodes

Every phase prints one line: its worst error (abs, and relative to the
reference's largest entry), the dispatch paths it ran, and its wall seconds
including compilation (a smoke figure, not a benchmark).  A failed phase
prints FAIL and the run exits 1; without a TPU it exits 2 before any phase.
The last line is one JSON object naming the device.  The model and the
references run under ``default_matmul_precision("highest")``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.paper_gnn import CONFIG  # noqa: E402
from repro.data.pipeline import random_graph, random_sparse_dense  # noqa: E402
from repro.dispatch import clear_log, dispatch_log  # noqa: E402
from repro.models.gnn import (build_graph, gat_forward, gcn_forward,  # noqa: E402
                              init_gat, init_gcn)
from repro.sparse import SparseMatrix, sample  # noqa: E402

REL_TOL = 1e-3  # worst |err| / max |reference|, float32 at highest precision


@dataclasses.dataclass(frozen=True)
class Sizes:
    graph_nodes: int = 16384
    avg_degree: int = 8
    train_steps: int = 3
    kernel_n: int = 16384
    kernel_d: int = 256
    ell_density: float = 0.10
    sell_density: float = 0.01
    serve_requests: int = 32
    serve_nodes: tuple = (256, 2048)


class PhaseError(AssertionError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def _errors(out, ref):
    """(max abs error, max abs error / max |ref|) over matching pytrees."""
    worst_abs, worst_rel = 0.0, 0.0
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        o = jnp.asarray(o, jnp.float32)
        r = jnp.asarray(r, jnp.float32)
        _check(o.shape == r.shape, f"shape {o.shape} != reference {r.shape}")
        _check(bool(jnp.isfinite(o).all()), "non-finite output")
        err = float(jnp.abs(o - r).max())
        scale = max(float(jnp.abs(r).max()), 1e-30)
        worst_abs, worst_rel = max(worst_abs, err), max(worst_rel, err / scale)
    return worst_abs, worst_rel


def _within(abs_err: float, rel_err: float) -> str:
    _check(rel_err <= REL_TOL,
           f"relative error {rel_err:.3e} exceeds {REL_TOL:.0e}")
    return f"max_abs={abs_err:.3e} max_rel={rel_err:.3e}"


def _plans(op=None, vjp=False):
    """Distinct (op, path, use_kernel) of the plans logged since clear_log."""
    return sorted({(p.op, p.path, p.use_kernel) for p in dispatch_log()
                   if (op is None or p.op == op)
                   and (p.policy == "vjp") == vjp})


def _fmt_plans(plans) -> str:
    return ",".join(f"{op}->{path}(use_kernel={uk})"
                    for op, path, uk in plans)


def _kernel_plans(plans, path: str) -> None:
    _check(bool(plans), "no plan was logged")
    for op, p, uk in plans:
        _check(p == path and uk,
               f"{op} ran {p} with use_kernel={uk}, expected {path} kernel")


# ---------------------------------------------------------------------------
# Dense references (plain jax.numpy, independent of the sparse stack)
# ---------------------------------------------------------------------------


def gcn_normalize(adj: np.ndarray) -> np.ndarray:
    a = adj.astype(np.float32) + np.eye(adj.shape[0], dtype=np.float32)
    dinv = 1.0 / np.sqrt(a.sum(1))
    return a * dinv[:, None] * dinv[None, :]


def gcn_dense(params, a_hat, x):
    h = x
    for i, w in enumerate(params["w"]):
        h = a_hat @ (h @ w)
        if i < len(params["w"]) - 1:
            h = jax.nn.relu(h)
    return h


def gat_dense(params, mask, x):
    h = x
    for i, w in enumerate(params["w"]):
        h = h @ w
        e = (h @ params["a_src"][i]) + (h @ params["a_dst"][i]).T
        e = jnp.where(mask, jax.nn.leaky_relu(e, 0.2), -jnp.inf)
        h = jax.nn.softmax(e, axis=1) @ h
        if i < len(params["w"]) - 1:
            h = jax.nn.elu(h)
    return h


def nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1).mean()


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def _graph_inputs(sizes: Sizes):
    n = sizes.graph_nodes
    adj = random_graph(n, avg_degree=sizes.avg_degree, seed=1)
    graph = build_graph(adj, CONFIG)
    # hyper-sparse graphs already carry SELL; a small graph gets it here
    graph = dataclasses.replace(graph, adj=graph.adj.with_form("sell"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, CONFIG.in_features))
                    .astype(np.float32))
    labels = jnp.asarray((np.arange(n) * CONFIG.n_classes // n)
                         .astype(np.int32))
    return adj, graph, x, labels


def phase_gcn_train(graph, x, labels, a_hat, policy: str, steps: int):
    """``a_hat``: the normalized adjacency as a host array; it visits the
    device only for the reference, so the step has the HBM to itself."""
    params = init_gcn(jax.random.PRNGKey(0), CONFIG)
    ref = gcn_dense(params, jnp.asarray(a_hat), x)

    @jax.jit
    def step(params, graph, x, labels):
        def loss_fn(p):
            logits = gcn_forward(p, graph, x, policy=policy)
            return nll(logits, labels), logits

        (loss, logits), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params = jax.tree_util.tree_map(lambda p, d: p - 0.05 * d, params, g)
        return params, loss, logits

    clear_log()
    losses = []
    for i in range(steps):
        params, loss, logits = step(params, graph, x, labels)
        if i == 0:
            errs = _errors(logits, ref)
        losses.append(float(loss))
    _check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    fwd, bwd = _plans("spmm"), _plans("sddmm", vjp=True)
    if policy != "auto":
        _kernel_plans(fwd + bwd, policy)
    return (f"{_within(*errs)} loss={losses[0]:.4f}->{losses[-1]:.4f} "
            f"paths={_fmt_plans(fwd + bwd)}")


def phase_gat(graph, x, labels, mask, policy: str):
    """``mask``: the edge pattern as a host array (device for the
    reference only)."""
    params = init_gat(jax.random.PRNGKey(0), CONFIG)

    def loss(p, graph, x, labels):
        out = gat_forward(p, graph, x, policy=policy, fuse=True)
        return nll(out, labels), out

    def ref_loss(p, mask, x, labels):
        out = gat_dense(p, mask, x)
        return nll(out, labels), out

    clear_log()
    (_, out), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params, graph, x, labels)
    (_, ref), g_ref = jax.jit(jax.value_and_grad(ref_loss, has_aux=True))(
        params, jnp.asarray(mask), x, labels)
    fwd, bwd = _plans("fused_attn"), _plans(vjp=True)
    _kernel_plans(fwd, policy)
    _kernel_plans([q for q in bwd if q[0] == "sddmm"], policy)
    out_errs, grad_errs = _errors(out, ref), _errors(g, g_ref)
    return (f"forward {_within(*out_errs)} grad {_within(*grad_errs)} "
            f"paths={_fmt_plans(fwd + bwd)}")


def phase_kernels(sizes: Sizes, path: str):
    n, d = sizes.kernel_n, sizes.kernel_d
    density = sizes.ell_density if path == "ell" else sizes.sell_density
    a = random_sparse_dense(n, density, seed=2)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(d, n)).astype(np.float32))
    A = SparseMatrix.from_dense(a, formats=(path,))
    a_dev = jnp.asarray(a)
    del a

    clear_log()
    y = jax.jit(lambda A, h: A.matmul(h, policy=path))(A, h)
    spmm_errs = _errors(y, a_dev @ h)
    del y
    # the gradient in A's stored values is an SDDMM on A's pattern
    da = jax.jit(jax.grad(
        lambda A, h, g: jnp.vdot(A.matmul(h, policy=path), g),
        allow_int=True))(A, h, b)
    grad_errs = _errors(A.with_data(da.data).densify(),
                        (b @ h.T) * (a_dev != 0))
    del da
    s = jax.jit(lambda A, b, c: sample(A, b, c, policy=path))(A, b, c)
    sddmm_errs = _errors(s.densify(), a_dev * (b @ c))
    fwd, bwd = _plans(), _plans(vjp=True)
    _kernel_plans(fwd, path)
    _kernel_plans([q for q in bwd if q[0] == "sddmm"], path)
    return (f"density={density} spmm {_within(*spmm_errs)} "
            f"values_grad {_within(*grad_errs)} "
            f"sddmm {_within(*sddmm_errs)} paths={_fmt_plans(fwd + bwd)}")


def phase_serve(sizes: Sizes):
    from repro.serve.runtime.continuous import (ContinuousBatchEngine,
                                                ContinuousConfig)

    params = init_gcn(jax.random.PRNGKey(0), CONFIG)
    rng = np.random.default_rng(4)
    lo, hi = sizes.serve_nodes
    reqs = []
    for i in range(sizes.serve_requests):
        n = int(rng.integers(lo, hi + 1))
        adj = random_graph(n, avg_degree=sizes.avg_degree, seed=100 + i)
        x = rng.normal(size=(n, CONFIG.in_features)).astype(np.float32)
        reqs.append((build_graph(adj, CONFIG), x, gcn_normalize(adj)))
    quarantined0 = obs.REGISTRY.total("resilience_quarantined_total")
    degraded0 = obs.REGISTRY.total("resilience_degraded_total")
    with ContinuousBatchEngine.for_gcn(
            params, cfg=ContinuousConfig(slots=8)) as engine:
        futures = [engine.submit(g, x) for g, x, _ in reqs]
        engine.drain(timeout=900)
        results = [f.result(timeout=0) for f in futures]
        forms = sorted({lane["form"]
                        for lane in engine.report()["lanes"].values()})
    quarantined = obs.REGISTRY.total("resilience_quarantined_total") \
        - quarantined0
    degraded = obs.REGISTRY.total("resilience_degraded_total") - degraded0
    _check(quarantined == 0 and degraded == 0,
           f"{quarantined} quarantined, {degraded} degraded requests")
    errs = [_errors(y, gcn_dense(params, jnp.asarray(a_hat), jnp.asarray(x)))
            for y, (_, x, a_hat) in zip(results, reqs)]
    worst = (max(e[0] for e in errs), max(e[1] for e in errs))
    return (f"resolved={len(results)}/{sizes.serve_requests} "
            f"quarantined={quarantined:g} degraded={degraded:g} "
            f"{_within(*worst)} lane_forms={','.join(forms)}")


def _run(name: str, fn, failures: list) -> None:
    t0 = time.perf_counter()
    try:
        with jax.default_matmul_precision("highest"):
            detail = fn()
        status = "ok"
    except Exception as exc:  # noqa: BLE001 - reported, then exit 1
        traceback.print_exc()
        detail = f"{type(exc).__name__}: {str(exc).splitlines()[0][:300]}"
        status = "FAIL"
        failures.append(name)
    print(f"phase {name}: {status} {detail} "
          f"wall_s_incl_compile={time.perf_counter() - t0:.1f}", flush=True)


def run_phases(sizes: Sizes = Sizes()) -> list:
    """Every one-chip phase; returns the names of the failed ones."""
    failures: list = []
    adj, graph, x, labels = _graph_inputs(sizes)
    a_hat = gcn_normalize(adj)
    del adj
    for policy in ("ell", "sell", "auto"):
        _run(f"gcn_train/{policy}", lambda: phase_gcn_train(
            graph, x, labels, a_hat, policy, sizes.train_steps), failures)
    for policy in ("ell", "sell"):
        _run(f"gat/{policy}",
             lambda: phase_gat(graph, x, labels, a_hat != 0, policy),
             failures)
    del a_hat, graph
    for path in ("ell", "sell"):
        _run(f"kernels/{path}", lambda: phase_kernels(sizes, path), failures)
    _run("serve", lambda: phase_serve(sizes), failures)
    return failures


def phase_sharded(sizes: Sizes):
    """spmm_1p5d (4-way row mesh) and spmm_2d (2x2 mesh) with the Pallas
    kernel against the one-chip Block-ELL kernel."""
    from jax.sharding import Mesh

    from repro.core.distributed import spmm_1p5d, spmm_2d
    from repro.kernels.spmm.ops import spmm_blockell

    n, d = sizes.kernel_n, sizes.kernel_d
    a = random_sparse_dense(n, sizes.ell_density, seed=2)
    ell = SparseMatrix.from_dense(a, formats=("ell",)).form("ell")
    h = jnp.asarray(np.random.default_rng(3).normal(size=(n, d))
                    .astype(np.float32))
    one_chip = jax.jit(lambda e, x: spmm_blockell(e, x))(ell, h)
    dense_errs = _errors(one_chip, jnp.asarray(a) @ h)
    devices = np.asarray(jax.devices()[:4])
    meshes = {"1p5d": (spmm_1p5d, Mesh(devices.reshape(4), ("data",))),
              "2d": (spmm_2d, Mesh(devices.reshape(2, 2),
                                   ("data", "model")))}
    parts = [f"one_chip_vs_dense {_within(*dense_errs)}"]
    for name, (fn, mesh) in meshes.items():
        y = jax.jit(lambda e, x: fn(e, x, mesh))(ell, h)
        shard_devs = sorted({s.device.id for s in y.addressable_shards})
        _check(len(shard_devs) == 4,
               f"{name}: output shards on devices {shard_devs}")
        shards = ";".join(
            f"rows{s.index[0].start or 0}/cols{s.index[1].start or 0}"
            f"@{s.device.id}" for s in y.addressable_shards)
        parts.append(f"{name}_vs_one_chip "
                     f"{_within(*_errors(y, one_chip))} shards=[{shards}]")
    return " ".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) found", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.chips == 4:
        failures: list = []
        _run("sharded_spmm", lambda: phase_sharded(Sizes()), failures)
    else:
        failures = run_phases()
    if failures:
        print(f"chip_smoke: failed phases: {', '.join(failures)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
