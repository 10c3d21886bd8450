"""Drive one run of a cell on the CPU at a tiny size, past the look for
a chip: the dispatcher is told the backend is a TPU, so it plans the
Pallas kernels, and every kernel runs in Pallas' TPU interpret mode."""
from __future__ import annotations

import contextlib
import copy
import json
import time

import jax
from jax.experimental.pallas import tpu as pltpu

from bench import harness
from bench.run import Run, driver

TINY_GRAPH = {"graph_nodes": 512, "graph_avg_degree": 2}


def tiny_inputs(workload: str):
    bench = harness.benchmark()
    cell, config, traffic = harness.cell_inputs(bench, workload)
    return bench, cell, dict(config, **TINY_GRAPH), traffic


@contextlib.contextmanager
def steered(monkeypatch):
    """TPU peaks, a backend that says TPU, and interpret mode."""
    from jax._src import config as jax_config

    v5e = harness.load_json(harness.BENCH_DIR / "peaks.json")["devices"][
        "TPU v5 lite"]
    monkeypatch.setattr(harness, "peaks", lambda kind: v5e)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    pltpu.set_tpu_interpret_mode()
    try:
        yield
    finally:
        jax_config.pallas_tpu_interpret_mode_context_manager.set_global(None)
        jax.config.update("jax_default_matmul_precision", precision)


def run_tiny(workload: str, capsys, *, seed=2 ** 31 + 7, seconds=1.0,
             trace=False, inputs=None):
    """The result line of a tiny run (its JSON) and everything printed."""
    bench, cell, config, traffic = inputs or tiny_inputs(workload)
    run = Run(copy.deepcopy(bench), cell, config, traffic, seed, seconds,
              trace, jax.devices(), time.time())
    driver(traffic).run_cell(run)
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out
