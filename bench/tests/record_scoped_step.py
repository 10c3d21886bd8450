"""Record the scoped reference trace and time one ``obs.span``, on a TPU.

    python3 bench/tests/record_scoped_step.py [--out PATH]

The trace (``data/v5e_scoped_gcn_step.xplane.pb`` by default) holds one
training step of the ``paper-gcn`` configuration on a 1,024-node graph
(auto plans the SELL kernels there, as in ``gcn-train-pl16k``), after
set-up's three steps, inside a ``bench.window`` annotation; the host
tracer keeps annotations only, so the file stays small.  Then the host
cost of one ``obs.span`` enter and exit is timed with no profiler
running and under an active one, beside a bare
``jax.profiler.TraceAnnotation``.  Prints the layer reduction's note
and one JSON line; exits 3 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import harness, scopes, trace  # noqa: E402

NODES = 1024
OUT = pathlib.Path(__file__).parent / "data" / "v5e_scoped_gcn_step.xplane.pb"
WORK = ROOT / "bench_out" / "scoped_step"


def _options(host_level: int):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = host_level
    options.enable_hlo_proto = False
    return options


def record(out: pathlib.Path) -> scopes.Layers:
    import jax

    from bench.drivers import train

    _, config, traffic = harness.cell_inputs(harness.benchmark(),
                                             "gcn-train-pl16k")
    config = dict(config, graph_nodes=NODES)
    harness.configure_jax(config)
    s = train.Session(config, traffic, seed=1)
    print(f"plans: {train.plans_summary(s.plans)}", flush=True)
    where = WORK / "trace"
    shutil.rmtree(where, ignore_errors=True)
    annotate = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(where), profiler_options=_options(1))
    try:
        with annotate("bench.window"):
            with annotate("bench.step"):
                s.params, loss, _ = s.step(s.params, s.graph, s.x,
                                           s.labels)
            with annotate("bench.readback"):
                float(loss)
                jax.block_until_ready(s.params)
    finally:
        jax.profiler.stop_trace()
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(trace.find_xplane(str(where)), out)
    return scopes.reduce(scopes.load(str(out)))


def _per_call_us(enter, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        with enter("bench.span_cost"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def span_cost(n: int = 20000) -> dict:
    """Microseconds per enter/exit, best of five rounds of ``n``."""
    import jax

    from repro.obs import MetricsRegistry, Tracer

    tracer = Tracer(registry=MetricsRegistry())
    kinds = {"obs_span": tracer.span,
             "trace_annotation": jax.profiler.TraceAnnotation}

    def best(enter):
        return min(_per_call_us(enter, n) for _ in range(5))

    out = {f"{k}_us_profiler_off": best(v) for k, v in kinds.items()}
    where = WORK / "span_cost"
    shutil.rmtree(where, ignore_errors=True)
    jax.profiler.start_trace(str(where), profiler_options=_options(1))
    try:
        out.update({f"{k}_us_profiler_on": best(v)
                    for k, v in kinds.items()})
    finally:
        jax.profiler.stop_trace()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=pathlib.Path, default=OUT)
    args = ap.parse_args(argv)
    devices = harness.accelerator(1)
    if devices is None:
        return 3
    got = record(args.out)
    print(got.note(), flush=True)
    busy = sum(got.seconds.values())
    print(json.dumps({
        "device": devices[0].device_kind, "trace": str(args.out),
        "trace_bytes": os.path.getsize(args.out), "busy_s": busy,
        "attributed_pct": 100.0 * (busy - got.seconds.get(
            scopes.UNATTRIBUTED, 0.0)) / busy,
        "layer_s": got.seconds, **span_cost()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
