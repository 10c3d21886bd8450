"""Full-graph training: one jitted step after another on the whole graph.

Each step is ``value_and_grad`` of the NLL over every node plus an SGD
update, with the parameters donated and the loss read back every step.
Set-up builds the graph and the step, drives the step from the seed
through its first ``PROBE_STEPS`` steps (the first compiles), and hands
the same step and state to the window.  Correctness compares those
first steps with the plain reference (``bench.reference``).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference

PROBE_STEPS = 3


def nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1).mean()


def sgd(params, grads, lr: float):
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)


def program_forward(model: str):
    from repro.models.gnn import gat_forward, gcn_forward

    if model == "gcn":
        return lambda p, g, x: gcn_forward(p, g, x, policy="auto")
    return lambda p, g, x: gat_forward(p, g, x, policy="auto", fuse=True)


def make_step(model: str, lr: float):
    forward = program_forward(model)

    def step(params, graph, x, labels):
        def loss_fn(p):
            logits = forward(p, graph, x)
            return nll(logits, labels), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        return sgd(params, grads, lr), loss, logits

    return jax.jit(step, donate_argnums=(0,))


@dataclasses.dataclass
class Inputs:
    n: int
    rows: np.ndarray
    cols: np.ndarray


def make_graph(config: dict) -> Inputs:
    """The configuration's graph: a dataset, the same for every run, so
    every seed trains the same compiled step."""
    rows, cols = gen.skewed_graph(config["graph_nodes"],
                                  config["graph_avg_degree"],
                                  gen.rng_for(config["graph_seed"], "graph"),
                                  config["graph_pareto_shape"])
    return Inputs(config["graph_nodes"], rows, cols)


def gnn_config(config: dict):
    from repro.configs.paper_gnn import GNNConfig

    return GNNConfig(name=config["name"], kind=config["model"],
                     n_layers=config["n_layers"],
                     in_features=config["in_features"],
                     hidden=config["hidden"], n_classes=config["n_classes"],
                     block_m=config["block_m"], block_n=config["block_n"])


def build_program_graph(config: dict, g: Inputs):
    from repro.models.gnn import build_graph

    dense = gen.dense_adjacency(g.n, g.rows, g.cols)
    graph = build_graph(dense, gnn_config(config))
    del dense
    return jax.block_until_ready(graph)


def seeded_state(config: dict, n: int, seed: int):
    params = jax.jit(lambda k: gen.make_params(config, k))(
        gen.jax_key(seed, "weights"))
    x, labels = jax.jit(lambda k: gen.make_node_data(config, n, k))(
        gen.jax_key(seed, "nodes"))
    return jax.block_until_ready((params, x, labels))


def host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


class Session:
    """The compiled step, its state and what its first steps produced."""

    def __init__(self, config: dict, traffic: dict, seed: int, built=None):
        """``built``: the (inputs, program graph) of an earlier session
        of the same configuration, to skip building the graph again."""
        self.config = config
        self.lr = traffic["lr"]
        if built is None:
            inputs = make_graph(config)
            built = (inputs, build_program_graph(config, inputs))
        self.inputs, self.graph = built
        self.params, self.x, self.labels = seeded_state(
            config, self.inputs.n, seed)
        self.step = make_step(config["model"], self.lr)
        self.p0 = host(self.params)
        losses = []
        for i in range(PROBE_STEPS):
            self.params, loss, logits = self.step(
                self.params, self.graph, self.x, self.labels)
            losses.append(float(loss))
            if i == 0:
                self.logits1 = np.asarray(logits)
                self.p1 = host(self.params)
        self.p_end = host(self.params)
        self.losses = np.asarray(losses)
        from repro.dispatch import dispatch_log

        self.plans = dispatch_log()

    def run_window(self, seconds: float, annotate) -> Dict[str, float]:
        """Steps until ``seconds`` have passed; returns counts and time.

        Each step ends when its updated parameters are ready as well as
        its loss, so that the window holds the whole work of every step
        it counts."""
        steps, bad = 0, 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with annotate("bench.step"):
                self.params, loss, _ = self.step(
                    self.params, self.graph, self.x, self.labels)
            with annotate("bench.readback"):
                value = float(loss)
                jax.block_until_ready(self.params)
            steps += 1
            bad += not np.isfinite(value)
            if time.perf_counter() >= deadline:
                break
        return {"steps": steps, "failed": bad,
                "window_s": time.perf_counter() - t0}

    def free(self) -> None:
        del self.params, self.graph, self.x, self.labels, self.step
        gc.collect()


def reference_run(config: dict, traffic: dict, seed: int,
                  precision: str, inputs: Inputs):
    """The reference's first steps from the same seed (made anew)."""
    params, x, labels = seeded_state(config, inputs.n, seed)
    edges = reference.reference_edges(config["model"], inputs.n,
                                      inputs.rows, inputs.cols)
    return reference.train_reference(config["model"], params, edges, x,
                                     labels, traffic["lr"], PROBE_STEPS,
                                     precision)


def _leaves(tree):
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def _norm_gap(prog, ref):
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    pn = np.array([np.linalg.norm(a) for a in prog])
    rn = np.array([np.linalg.norm(a) for a in ref])
    scale = np.maximum(rn, np.median(rn))
    return float(np.max(np.abs(pn - rn) / scale))


def readings(lr, losses, logits1, p0, p1, p_end, ref) -> Dict[str, float]:
    """The numbers compared with their limits.

    ``grad_norm_gap``: step 1's gradient as SGD applied it, (p0 - p1)/lr,
    against the reference's.  ``update_norm_gap``: p_end - p0 after the
    first steps; leaves whose reference gradient is under a thousandth
    of the median leaf's move by rounding alone and are left out.
    """
    r_losses, r_logits, r_grad, r_end = ref
    l0, l1, le = _leaves(p0), _leaves(p1), _leaves(p_end)
    rg, re = _leaves(r_grad), _leaves(r_end)
    grad = [(a - b) / lr for a, b in zip(l0, l1)]
    gnorm = np.array([np.linalg.norm(g) for g in rg])
    moving = gnorm >= 1e-3 * np.median(gnorm)
    upd = [b - a for a, b, m in zip(l0, le, moving) if m]
    r_upd = [b - a for a, b, m in zip(l0, re, moving) if m]
    logits1 = np.asarray(logits1, np.float64)
    r_logits = np.asarray(r_logits, np.float64)
    return {
        "loss_gap": float(np.max(np.abs(losses - r_losses)
                                 / np.abs(r_losses))),
        "logits_gap": float(np.max(np.abs(logits1 - r_logits))
                            / np.max(np.abs(r_logits))),
        "grad_norm_gap": _norm_gap(grad, rg),
        "update_norm_gap": _norm_gap(upd, r_upd),
    }


def session_readings(s: Session, traffic: dict, seed: int) -> Dict[str, float]:
    ref = reference_run(s.config, traffic, seed, "highest", s.inputs)
    return readings(s.lr, s.losses, s.logits1, s.p0, s.p1, s.p_end, ref)


def control_readings(config: dict, traffic: dict, seed: int,
                     inputs: Inputs) -> Dict[str, float]:
    """The reference one precision step down in the program's place."""
    lr = traffic["lr"]
    r_losses, r_logits, r_grad, r_end = reference_run(
        config, traffic, seed, "high", inputs)
    params, _, _ = seeded_state(config, inputs.n, seed)
    p0 = host(params)
    p1 = jax.tree_util.tree_map(lambda a, g: a - lr * g, p0, r_grad)
    ref = reference_run(config, traffic, seed, "highest", inputs)
    return readings(lr, r_losses, r_logits, p0, p1, r_end, ref)


def plans_summary(plans) -> list:
    return sorted({f"{p.op}:{p.policy}->{p.path}"
                   f"{'(kernel)' if p.use_kernel else ''}"
                   f"{'+' + p.fused if p.fused else ''}" for p in plans})


def graph_entries(inputs: Inputs) -> int:
    """Stored entries of the normalised adjacency (A + I)."""
    return len(reference.attention_edges(inputs.n, inputs.rows,
                                         inputs.cols)[0])


def run_cell(run) -> None:
    """One benchmark run of a full-graph training cell (see ``bench.run``)."""
    from bench import harness, trace, work

    config, traffic = run.config, run.traffic
    s = Session(config, traffic, run.seed)
    run.note(f"dispatch plans: {plans_summary(s.plans)}")
    run.window_started()
    before = run.compiles.count
    with harness.traced(run.trace) as tr:
        win = s.run_window(run.seconds, run.annotate)
    run.note(f"compiles in window: {run.compiles.count - before}; "
             f"steps {win['steps']} in {win['window_s']:.3f} s")
    device = run.device_info()
    s.free()
    got = session_readings(s, traffic, run.seed)
    nnz = graph_entries(s.inputs)
    ctx = {"window_s": win["window_s"], "steps": win["steps"],
           "peaks": run.peaks, "graph_n": s.inputs.n, "graph_nnz": nnz,
           "work_step": work.TRAIN_STEP[config["model"]](
               config, s.inputs.n, nnz),
           "kernels": planned_kernels(s.plans),
           "trace": trace.load(tr.path) if tr.path else None}
    metrics = {"train_step_ms": 1e3 * win["window_s"] / win["steps"]}
    run.finish(attempted=win["steps"], failed=win["failed"],
               e2e=metrics, ctx=ctx, readings=got, device=device)


def planned_kernels(plans) -> set:
    """The Pallas kernels that the step's forward and backward plans
    (``dispatch_log``, the custom VJP's included) route products to."""
    from bench import work

    names = {work.planned_kernel(p.op, p.path, p.fused)
             for p in plans if p.use_kernel}
    return names - {None}
