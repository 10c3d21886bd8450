"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration under
``bench/configs/`` and its traffic mix under ``bench/traffic/``, and
hands them to the driver of the mix's ``kind``,
``bench/drivers/<kind>.py``.  It measures the chip only: without a
TPU, or with fewer chips than the cell asks for, it exits 3 and prints
no result.  The last line of standard output is the
result as one JSON object; the numbers compared for ``correct`` are
the last lines of standard error.
"""
from __future__ import annotations

import argparse
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # the checkout root, not bench/, heads the path (bench/trace.py must
    # not shadow the standard library's ``trace``); src/ holds the program
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import harness  # noqa: E402



def driver(traffic: dict):
    """The module that runs cells of this traffic mix's ``kind``."""
    import importlib

    return importlib.import_module(f"bench.drivers.{traffic['kind']}")


class Run:
    """One run of one cell: its inputs, its clock and its result."""

    def __init__(self, bench, cell, config, traffic, seed, seconds, trace,
                 devices, t_start):
        import jax

        self.bench, self.cell = bench, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.t_start = devices, t_start
        self.compiles = harness.CompileCounter()
        self.peaks = harness.peaks(devices[0].device_kind)
        self.annotate = jax.profiler.TraceAnnotation
        self.setup_s = None
        self.printed = False

    def note(self, msg: str) -> None:
        """A line for the reader of the run's output, before the result."""
        print(f"bench: {msg}", flush=True)

    def window_started(self) -> None:
        self.setup_s = time.time() - self.t_start
        self.note(f"set-up {self.setup_s:.3f} s")

    def device_info(self) -> dict:
        return harness.device_info(self.devices, self.cell["chips"])

    def finish(self, *, attempted, failed, e2e, ctx, readings, device):
        from bench import trace as tr

        lims = harness.limits(self.cell["name"])
        ok, checks = harness.judge(readings, lims)
        rest = {k: v for k, v in readings.items() if k not in lims}
        if rest:
            self.note(f"read, not compared: {rest}")
        result = {"correct": bool(ok and failed == 0),
                  "attempted": int(attempted), "failed": int(failed)}
        breakdown = None
        if self.trace:
            t = ctx["trace"]
            lo, hi = t.window()
            device["busy_s"] = tr.busy_ns(t) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            metrics = harness.per_layer_metrics(self.bench, self.cell, ctx)
            breakdown = tr.breakdown(t)
            for line in ctx.get("notes", []):
                self.note(line)
        else:
            declared = {m["name"]: m for m in self.bench["end_to_end"]
                        if self.cell["name"] in m.get("workloads",
                                                      [self.cell["name"]])}
            e2e = dict(e2e, setup_s=self.setup_s)
            metrics = {k: {"value": v, "unit": declared[k]["unit"]}
                       for k, v in e2e.items() if k in declared}
        result["metrics"] = metrics
        result["device"] = device
        if breakdown is not None:
            result["breakdown"] = breakdown
        harness.print_result(result, checks)
        self.printed = True


def main(argv=None) -> int:
    t_start = harness.process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (args.seconds > 0 and math.isfinite(args.seconds)):
        ap.error("--seconds must be positive")

    bench = harness.benchmark()
    cell, config, traffic = harness.cell_inputs(bench, args.workload)
    devices = harness.accelerator(cell["chips"])
    if devices is None:
        return 3
    print(f"bench: device platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}", flush=True)
    harness.configure_jax(config)
    run = Run(bench, cell, config, traffic, args.seed, args.seconds,
              bool(args.trace), devices, t_start)
    driver(traffic).run_cell(run)
    return 0 if run.printed else 1


if __name__ == "__main__":
    sys.exit(main())
