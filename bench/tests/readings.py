"""Readings that the limits in ``bench/limits/`` are set from, on the chip.

    python3 bench/tests/readings.py --workload gcn-train-pl16k \\
        --seeds 1 2 3 --control-seeds 1 2 3 --fault-seeds 1 2 3

In one process, at the cell's own size: the program's numbers on each of
``--seeds``; the control's (the reference one precision step down, in the
program's place) on each of ``--control-seeds``; for a training cell,
the program with half of the batch left out of the loss on each of
``--fault-seeds``.  A program seed on which a number reads over ten
times its median, and each of ``--witness-seeds``, also runs the
program's unfused path and the control, as witnesses of whether the
reference itself swings there.  One JSON line per reading goes to stdout and to
``bench_out/readings-<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import harness  # noqa: E402


def half_batch_nll(nll):
    return lambda logits, labels: nll(logits[:logits.shape[0] // 2],
                                      labels[:labels.shape[0] // 2])


def leaf_detail(s, traffic, seed):
    """Per leaf: the norms of step 1's gradient and of the change after
    the first steps, program beside reference."""
    import jax
    import numpy as np

    from bench.drivers import train

    _, _, r_grad, r_end = train.reference_run(s.config, traffic, seed,
                                              "highest", s.inputs)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(s.p0)[0]]
    l0, l1, le = (train._leaves(t) for t in (s.p0, s.p1, s.p_end))
    rg, re = train._leaves(r_grad), train._leaves(r_end)
    rows = []
    for i, name in enumerate(names):
        rows.append({"leaf": name,
                     "grad": float(np.linalg.norm((l0[i] - l1[i]) / s.lr)),
                     "grad_ref": float(np.linalg.norm(rg[i])),
                     "update": float(np.linalg.norm(le[i] - l0[i])),
                     "update_ref": float(np.linalg.norm(re[i] - l0[i])),
                     "param": float(np.linalg.norm(l0[i]))})
    return rows


def unfused_forward(model: str):
    """The program's other path: each layer's aggregation unfused
    (separate SDDMM, softmax and SpMM for GAT; SpMM then bias and relu
    for GCN), planned by the same dispatcher."""
    from repro.models.gnn import gat_forward, gcn_forward

    fwd = gcn_forward if model == "gcn" else gat_forward
    return lambda p, g, x: fwd(p, g, x, policy="auto", fuse=False)


def session_as_reference(s):
    """A session's first steps in the shape ``train.readings`` takes as
    its reference: (losses, logits, step 1's gradient, params after)."""
    import jax

    grad = jax.tree_util.tree_map(lambda a, b: (a - b) / s.lr, s.p0, s.p1)
    return s.losses, s.logits1, grad, s.p_end


def outliers(program: dict) -> list:
    """Seeds on which some number reads over ten times its median over
    all program seeds."""
    import numpy as np

    out = []
    for name in next(iter(program.values()), {}):
        med = float(np.median([r[name] for r in program.values()]))
        out += [seed for seed, r in program.items()
                if med > 0 and r[name] > 10 * med and seed not in out]
    return out


def witness(config, traffic, seed, built, s, emit, control=True):
    """On one seed: the program's unfused path against the reference and
    against the fused program, and the control."""
    from bench.drivers import train

    fused = train.program_forward
    train.program_forward = unfused_forward
    try:
        w = train.Session(config, traffic, seed, built)
    finally:
        train.program_forward = fused
    emit("witness:unfused", seed, train.session_readings(w, traffic, seed))
    emit("program_vs_unfused", seed, train.readings(
        s.lr, s.losses, s.logits1, s.p0, s.p1, s.p_end,
        session_as_reference(w)))
    if control:
        emit("control", seed,
             train.control_readings(config, traffic, seed, built[0]))


def train_readings(config, traffic, args, emit):
    from bench.drivers import train

    inputs = train.make_graph(config)
    built = (inputs, train.build_program_graph(config, inputs))
    program = {}
    for seed in args.seeds:
        s = train.Session(config, traffic, seed, built)
        program[seed] = train.session_readings(s, traffic, seed)
        emit("program", seed, program[seed])
        if seed in args.detail_seeds:
            emit("leaves", seed, leaf_detail(s, traffic, seed))
        del s
    for seed in args.witness_seeds + outliers(program):
        s = train.Session(config, traffic, seed, built)
        emit("leaves", seed, leaf_detail(s, traffic, seed))
        witness(config, traffic, seed, built, s, emit,
                control=seed not in args.control_seeds)
        del s
    for seed in args.control_seeds:
        emit("control", seed,
             train.control_readings(config, traffic, seed, inputs))
    nll = train.nll
    train.nll = half_batch_nll(nll)
    try:
        for seed in args.fault_seeds:
            s = train.Session(config, traffic, seed, built)
            emit("fault:half_batch", seed,
                 train.session_readings(s, traffic, seed))
            del s
    finally:
        train.nll = nll


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--detail-seeds", type=int, nargs="*", default=[],
                    help="program seeds whose per-leaf norms are printed")
    ap.add_argument("--witness-seeds", type=int, nargs="*", default=[],
                    help="seeds that also run the unfused path and the "
                    "control; every outlying program seed does")
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell, config, traffic = harness.cell_inputs(bench, args.workload)
    if harness.accelerator(cell["chips"]) is None:
        return 3
    harness.configure_jax(config)
    out = harness.ROOT / "bench_out" / f"readings-{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    t0 = time.time()

    with open(out, "a") as f:
        def emit(kind, seed, got):
            line = json.dumps({"workload": args.workload, "kind": kind,
                               "seed": seed, "readings": got,
                               "t_s": round(time.time() - t0, 1)})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        train_readings(config, traffic, args, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
