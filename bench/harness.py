"""What every cell shares: paths, the device, compile counting, limits,
per-layer metric readers and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import sys
from typing import Callable, Dict

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = ROOT / "bench_out" / "trace"


def process_start() -> float:
    """Epoch seconds at which this process started."""
    import psutil

    return psutil.Process().create_time()


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_inputs(bench: dict, workload: str):
    """(cell, configuration, traffic mix) of a workload, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def limits(workload: str) -> Dict[str, float]:
    return load_json(BENCH_DIR / "limits" / f"{workload}.json")["limits"]


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def accelerator(chips: int):
    """The accelerator devices, or None (with the reason on stderr)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); this "
              "benchmark measures the chip only", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chip(s), found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def device_info(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


class CompileCounter:
    """Counts XLA executables built or loaded from the persistent cache
    (JAX's backend-compile event), so a window can show it compiled
    nothing."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


def configure_jax(config: dict) -> str:
    """Compile cache inside the checkout, for small programs too, and
    the matmul precision the configuration states."""
    import jax
    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    return where


@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the enclosed window into ``bench_out/trace`` and yield a
    holder whose ``path`` names the written ``.xplane.pb``."""
    import shutil

    import jax

    holder = type("Traced", (), {"path": None})()
    if not enabled:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield holder
        return
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    # no Python function tracing: it slows the host several-fold, and the
    # per-layer metrics need device ops and bench.* annotations only
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield holder
    finally:
        jax.profiler.stop_trace()
    from bench.trace import find_xplane

    holder.path = find_xplane(str(TRACE_DIR))


def metric_reader(name: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_metrics(bench: dict, cell: dict, ctx: dict) -> dict:
    """Every per-layer metric declared for this cell that finds
    something to read."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    out = {}
    for m in bench["per_layer"]:
        listed = m.get("workloads")
        if listed is not None and cell["name"] not in listed:
            continue
        if listed is None and m["moves"] not in reported:
            continue
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(readings: Dict[str, float], lims: Dict[str, float]):
    """(correct, [[name, reading, limit], ...]) — a reading that is
    missing or not finite fails."""
    rows, ok = [], True
    for name, limit in lims.items():
        value = readings.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        rows.append([name, value, limit])
    return ok, rows


def print_result(result: dict, checks) -> None:
    """The compared numbers as the last lines of stderr, then the
    result as the last line of stdout (checks under a last key)."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    print(json.dumps(result), flush=True)
