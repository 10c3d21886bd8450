"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  bench_dense_limit  — Fig. 2 (dense-format wall)
  bench_footprint    — Fig. 8 (SELLPACK-like vs CSR footprint)
  bench_spmm         — Fig. 9 (SpMM vs density/N, d=256)
  bench_sddmm        — Fig. 10 (SDDMM vs density, d=2, mnz sensitivity)
  bench_crossover    — Fig. 9's crossover as a dispatch-path sweep
  bench_serve        — batched-serving throughput/latency sweep (also
                       writes BENCH_serve.json) + the adaptive-runtime
                       comparison on a drifting mix (bench_serve_adaptive,
                       writes BENCH_serve_adaptive.json)
  bench_fused        — fused-vs-unfused GCN epilogue + GAT attention
                       sweep (also writes BENCH_fused.json)
  bench_corpus       — structured-matrix corpus (uniform/powerlaw/rmat/
                       banded/block_pruned) over every execution path +
                       the SpMV lane (also writes BENCH_corpus.json)
  bench_serve_fleet  — multi-worker fleet with a mid-run worker kill:
                       throughput + p99 before/during/after failover,
                       requests-lost must be 0 (writes BENCH_fleet.json)

``python -m benchmarks.run [--full] [--policy auto] [--json out.json]``
(quick mode by default so the CPU container finishes in minutes; --full
matches the paper's largest sizes; --policy sets the dispatch policy for
the benches that route through the dispatch layer; --json additionally
dumps every emitted row plus the plan-cache counters as JSON;
--calibrate runs the ``dispatch.autotune.calibrate`` microbenchmark
first and prices the spmm/sddmm benches with the measured constants,
round-tripped through an ``AutotuneCache`` save/load).

When both kernel benches (spmm + sddmm) run with ``--json``, their rows
are additionally written to ``BENCH_kernels.json`` — the committed
kernel-performance baseline future PRs regress against (the CI
bench-smoke job refreshes it as an artifact every push).
"""
import argparse
import json
import sys

KERNELS_BASELINE = "BENCH_kernels.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "autotune", "ell", "sell", "csr",
                             "dense"])
    ap.add_argument("--api", default="sparse", choices=["legacy", "sparse"],
                    help="dispatch surface for the spmm/sddmm benches")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows as JSON to PATH")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure the cost-model constants on this "
                         "backend first and use them for the kernel "
                         "benches (persisted via AutotuneCache)")
    ap.add_argument("--obs-snapshot", default=None, metavar="PATH",
                    help="write repro.obs.snapshot() (metrics, span "
                         "summary, retrace sentry, cost audit) as JSON "
                         "after the benches finish")
    args = ap.parse_args()
    quick = not args.full

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_corpus, bench_crossover,
                            bench_dense_limit, bench_footprint, bench_fused,
                            bench_sddmm, bench_serve, bench_serve_fleet,
                            bench_spmm, common)
    from repro.sparse import plan_cache_stats
    benches = {
        "dense_limit": bench_dense_limit.run,
        "footprint": bench_footprint.run,
        "spmm": bench_spmm.run,
        "sddmm": bench_sddmm.run,
        "crossover": bench_crossover.run,
        "serve": bench_serve.run,
        "fused": bench_fused.run,
        "corpus": bench_corpus.run,
        "fleet": bench_serve_fleet.run,
    }
    dispatched = {"spmm", "sddmm", "crossover", "serve", "fused", "corpus"}
    api_axis = {"spmm", "sddmm"}
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(benches)
        if unknown:
            ap.error(f"unknown bench name(s) {sorted(unknown)}; "
                     f"expected among {sorted(benches)}")
    common.reset_rows()
    print("name,us_per_call,derived")

    cost_model = None
    if args.calibrate:
        import os
        import tempfile

        from repro.dispatch import AutotuneCache, calibrate

        print("# --- calibrate ---", file=sys.stderr)
        cache = AutotuneCache()
        calibrate(n=256 if quick else 1024, d=64,
                  densities=(0.5, 0.05, 0.005), cache=cache)
        # the calibration must survive the cache's JSON round-trip —
        # that is how a serving host would pick it up next process
        fd, path = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            cache.save(path)
            reloaded = AutotuneCache()
            reloaded.load(path)
            cost_model = reloaded.cost_model
        finally:
            os.remove(path)
        common.emit("calibrate_constants", 0.0,
                    f"c_ell={cost_model.c_ell:.3g};"
                    f"c_sell={cost_model.c_sell:.3g};"
                    f"c_csr={cost_model.c_csr:.3g}")

    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        if name in api_axis:
            fn(quick=quick, policy=args.policy, api=args.api,
               cost_model=cost_model)
        elif name in dispatched:
            fn(quick=quick, policy=args.policy)
        else:
            fn(quick=quick)
    if args.obs_snapshot:
        from repro import obs

        with open(args.obs_snapshot, "w") as f:
            json.dump(obs.snapshot(), f, indent=2)
            f.write("\n")
        print(f"# wrote obs snapshot to {args.obs_snapshot}",
              file=sys.stderr)
    pc = plan_cache_stats()
    emitted = pc["hits"] + pc["misses"]
    rate = pc["hits"] / emitted if emitted else 0.0
    print(f"plan_cache,{pc['hits']},misses={pc['misses']};"
          f"hit_rate={rate:.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({
                "rows": common.ROWS,
                "plan_cache": {**pc, "hit_rate": round(rate, 3)},
            }, f, indent=2)
        print(f"# wrote {len(common.ROWS)} rows to {args.json}",
              file=sys.stderr)
        ran = set(benches) if only is None else only
        if {"spmm", "sddmm"} <= ran:
            kernel_rows = [r for r in common.ROWS
                           if r["name"].startswith(("spmm_", "sddmm_"))]
            with open(KERNELS_BASELINE, "w") as f:
                json.dump({
                    "quick": quick,
                    "policy": args.policy,
                    "api": args.api,
                    "rows": kernel_rows,
                }, f, indent=2)
                f.write("\n")
            print(f"# wrote {len(kernel_rows)} kernel rows to "
                  f"{KERNELS_BASELINE}", file=sys.stderr)


if __name__ == "__main__":
    main()
