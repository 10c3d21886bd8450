"""Roofline share of the sparse products that ran on the program's Pallas
kernels: the least time their required work could take over the device
time of their calls in the trace, found by kernel name.  Each call's
work (``bench.work.kernel_call``) is counted from the graph's stored
entries and the widths and dtypes of that call's own operands as the
trace shows them.  Every kernel call counts; a call of a kernel that no
dispatch plan names, or whose shapes cannot be read, leaves the whole
metric unreported, with a note."""
from bench import trace, work


def read(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    notes = ctx.setdefault("notes", [])
    peaks = ctx["peaks"]
    n, nnz, planned = ctx["graph_n"], ctx["graph_nnz"], ctx["kernels"]
    least, spent, seen = 0.0, 0.0, set()
    for name, (count, ns) in sorted(trace.op_totals(t, clip=False).items()):
        kernel = trace.base_name(name)
        if kernel not in work.KERNELS:
            continue
        if kernel not in planned:
            notes.append(f"sparse_roofline.train: {name} ran, but no plan "
                         "names it; not reported")
            return None
        w = work.kernel_call(kernel, t.op_text.get(name, ""), n, nnz)
        if w is None:
            notes.append(f"sparse_roofline.train: shapes of {name} not "
                         "read; not reported")
            return None
        seen.add(kernel)
        least += count * work.roofline_seconds(
            w, peaks["flops_per_s"], peaks["hbm_bytes_per_s"])[0]
        spent += ns / 1e9
    for kernel in sorted(set(planned) - seen):
        notes.append(f"sparse_roofline.train: {kernel} planned, no call "
                     "in the trace (its result unused)")
    return 100.0 * least / spent if spent > 0 else None
