"""The whole training step's share of the chip's peak: the least time the
step's required work could take (its operations over peak FLOP/s or its
minimal HBM bytes over peak bandwidth, whichever is longer; counted by
``bench.work`` from the widths and the graph) over the measured step
time of the traced window."""
from bench import work


def read(ctx):
    t = ctx.get("trace")
    if t is None or not any(t.device_ops.values()) or not ctx.get("steps"):
        return None
    peaks = ctx["peaks"]
    least_s, bound = work.roofline_seconds(
        ctx["work_step"], peaks["flops_per_s"], peaks["hbm_bytes_per_s"])
    ctx.setdefault("notes", []).append(f"step_mfu.train bound by {bound}")
    return 100.0 * least_s / (ctx["window_s"] / ctx["steps"])
