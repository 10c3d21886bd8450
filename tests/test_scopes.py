"""Every device op of a GNN training step names the program layer that
issued it, and ``obs`` spans reach the profiler's trace.

The step is compiled (not run) on a 512-node graph for each model and
forced path, on the jnp reference route the CPU plans and on the Pallas
kernel route (the backend steered to report TPU, kernels in interpret
mode).  Every gather, scatter and custom-call of the compiled HLO must
carry a ``sparse.*`` scope in its ``op_name`` (the models issue none of
their own), and every dot a ``sparse.*`` or ``gnn.*`` one.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.configs.paper_gnn import SMOKE_CONFIG as GCFG
from repro.data.pipeline import random_graph
from repro.models.gnn import (Graph, build_graph, gat_forward, gcn_forward,
                              init_gat, init_gcn)

N = 512
CHECKED = re.compile(
    r"^\s*(?:ROOT )?%\S+ = .*? (gather|scatter|dot|custom-call)\(")
# test.*: the test's own loss and SGD, the only ops outside the taxonomy
SPARSE = re.compile(r"(^|[/(])(sparse|test)\.")
SCOPED = re.compile(r"(^|[/(])(sparse|gnn|test)\.")


def _graph():
    g = build_graph(random_graph(N, avg_degree=4, seed=3), GCFG)
    return Graph(adj=g.adj.with_form("sell"), n_nodes=g.n_nodes)


def _compiled_step(model: str, path: str) -> str:
    init, forward = ((init_gcn, gcn_forward) if model == "gcn"
                     else (init_gat, gat_forward))
    params = init(jax.random.PRNGKey(0), GCFG)
    x = jnp.ones((N, GCFG.in_features), jnp.float32)
    labels = jnp.zeros((N,), jnp.int32)

    def step(p, graph, x, labels):
        def loss(p):
            logits = forward(p, graph, x, policy=path)
            with jax.named_scope("test.loss"):
                logp = jax.nn.log_softmax(logits)
                return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

        grads = jax.grad(loss)(p)
        with jax.named_scope("test.sgd"):
            return jax.tree_util.tree_map(lambda a, g: a - 0.1 * g, p,
                                          grads)

    return jax.jit(step).lower(params, _graph(), x, labels).compile() \
        .as_text()


@pytest.mark.parametrize("route", ["reference", "kernel"])
@pytest.mark.parametrize("path", ["sell", "csr", "ell"])
@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_every_sparse_op_names_its_layer(model, path, route, monkeypatch):
    if route == "kernel":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pltpu.force_tpu_interpret_mode():
            text = _compiled_step(model, path)
    else:
        text = _compiled_step(model, path)
    checked, bare = 0, []
    for line in text.splitlines():
        m = CHECKED.match(line)
        if not m:
            continue
        checked += 1
        name = re.search(r'op_name="([^"]*)"', line)
        rule = SCOPED if m.group(1) == "dot" else SPARSE
        if name is None or not rule.search(name.group(1)):
            bare.append(f"{m.group(1)}: "
                        f"{name.group(1) if name else line.strip()[:120]}")
    assert checked > 0
    assert bare == []
    if path != "csr" and route == "kernel":
        assert "/sparse.kernel." in text
    if path == "sell" and route == "kernel":
        assert "/sparse.layout.tile_values/" in text


def test_obs_span_lands_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    name = "test.span_on_the_profiler_clock"
    with jax.profiler.trace(str(tmp_path)):
        with obs.span(name):
            jnp.arange(8.0).sum().block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    assert [e.name for e in host].count(name) == 1
    (event,) = [e for e in host if e.name == name]
    assert event.duration_ns > 0
    # the ring and the span_ms histogram still record it
    assert obs.TRACER.spans(name)[-1].dur_ms > 0
    assert obs.REGISTRY.value("span_ms", span=name) >= 1


def test_graph_build_records_its_setup_spans():
    build_graph(random_graph(N, avg_degree=4, seed=3), GCFG)
    spans = obs.TRACER.spans()
    root = [s for s in spans if s.name == "gnn.build_graph"][-1]
    children = sorted(s.name for s in spans if s.parent_id == root.span_id)
    # a 512-node degree-4 graph is over 99% sparse: sell is packed too
    assert children == ["sparse.pack.csr", "sparse.pack.ell",
                        "sparse.pack.sell", "sparse.stats"]
    assert root.dur_ms >= sum(s.dur_ms for s in spans
                              if s.parent_id == root.span_id)
    assert np.isfinite(root.dur_ms)
