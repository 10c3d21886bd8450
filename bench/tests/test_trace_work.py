"""The trace reduction and the work counts, against hand-worked cases."""
import math
import pathlib

import pytest

from bench import trace, work
from bench.trace import Event, Trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "v5e_sell_spmm.xplane.pb"


def _trace():
    """Window 0..100 ns; device ops cover 10..30 (two overlapping ops),
    50..60 and 90..120 (clipped at 100); host annotations overlap the
    gaps."""
    ops = [Event("spmm_sell.1", 10, 25), Event("fusion.3", 20, 30),
           Event("spmm_sell.2", 50, 60), Event("spmm_sell_epilogue", 90, 120)]
    anns = [Event("bench.window", 0, 100), Event("bench.step", 0, 45),
            Event("bench.readback", 45, 95)]
    return Trace(device_ops={"/device:TPU:0": ops}, annotations=anns)


def test_busy_idle_and_gaps():
    t = _trace()
    assert t.window() == (0, 100)
    assert trace.busy_ns(t) == 20 + 10 + 10
    assert trace.idle_share_pct(t) == pytest.approx(60.0)
    assert trace.idle_gaps(t) == [(0, 10), (30, 50), (60, 90)]
    # 0..10 and 30..45 lie in bench.step; 45..50 and 60..90 in readback
    assert trace.gaps_by_annotation(t) == {"bench.step": 10 + 20,
                                           "bench.readback": 30}


def test_op_totals_clipped_or_whole_and_base_names():
    t = _trace()
    assert trace.op_totals(t)["spmm_sell_epilogue"] == (1, 10)
    assert trace.op_totals(t, clip=False)["spmm_sell_epilogue"] == (1, 30)
    assert trace.op_totals(t, clip=False)["spmm_sell.1"] == (1, 15)
    assert [trace.base_name(n) for n in
            ("spmm_sell.3", "spmm_sell_epilogue", "fusion.3", "a.b")] == [
        "spmm_sell", "spmm_sell_epilogue", "fusion", "a.b"]


def test_breakdown_is_in_seconds_and_ordered():
    b = trace.breakdown(_trace())
    assert b["device_ops"][0] == ["spmm_sell.1", 15e-9]
    assert b["idle_gaps"][0] == ["bench.step", 30e-9]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_ops_reads_nothing():
    t = Trace(device_ops={}, annotations=[Event("bench.window", 0, 5)])
    assert trace.idle_share_pct(t) is None
    assert trace.idle_gaps(t) == [(0, 5)]


def test_recorded_v5e_trace():
    """Two calls of a jitted SELL SpMM (256 x 256, 2% dense, 128 wide) on
    one v5e, each inside a ``bench.step`` annotation, 2 ms apart.  The
    device's clock ran about 1-2 ms behind the host's in this trace (each
    call's ops end before its annotation starts), so the window is taken
    as the span of the annotations and the ops together."""
    t = trace.load(str(FIXTURE))
    steps = [a for a in t.annotations if a.name == "bench.step"]
    assert len(steps) == 2
    ops = t.device_ops["/device:TPU:0"]
    t.annotations.append(Event(
        "bench.window", min(e.start_ns for e in steps + ops),
        max(e.end_ns for e in steps + ops)))
    lo, hi = t.window()
    busy = trace.busy_ns(t)
    assert 0 < busy < hi - lo
    kernels = {n: v for n, v in trace.op_totals(t).items()
               if trace.base_name(n) == "spmm_sell"}
    assert sum(c for c, _ in kernels.values()) == 2
    assert 0 < sum(ns for _, ns in kernels.values()) <= busy
    # op names are the HLO instruction names, not their whole text
    assert all(" " not in name for name in trace.op_totals(t))
    # the whole text keeps the shapes: the product is 128 wide
    (name,) = kernels
    assert work.kernel_call("spmm_sell", t.op_text[name], 256, 1311) == \
        work.spmm(1311, 256, 256, 128)


def test_spmm_sddmm_attention_dense_counts():
    # A: 4x5 with 10 entries, H: 5x3 -> Y: 4x3
    w = work.spmm(nnz=10, rows=4, cols=5, d=3)
    assert w.flops == 60
    assert w.bytes == 10 * 8 + 5 * 4 + (5 + 4) * 3 * 4
    # S at 10 entries of B[4,2] @ C[2,5]
    w = work.sddmm(nnz=10, rows=4, cols=5, k=2)
    assert w.flops == 40
    assert w.bytes == 10 * 4 + 5 * 4 + (4 + 5) * 2 * 4 + 10 * 4
    w = work.attention(nnz=10, n=4, d=3)
    assert w.flops == 10 * (1 + 6)
    assert w.bytes == 10 * 4 + 5 * 4 + 2 * 4 * 4 + 2 * 4 * 3 * 4
    w = work.dense(2, 3, 4)
    assert (w.flops, w.bytes) == (48, (6 + 12 + 8) * 4)


def test_gcn_step_counts_by_hand():
    cfg = {"n_layers": 2, "in_features": 4, "hidden": 3, "n_classes": 2}
    n, nnz = 5, 7
    a = lambda d: (2 * nnz * d, nnz * 8 + (n + 1) * 4 + 2 * n * d * 4)
    dense = lambda m, k, c: (2 * m * k * c, (m * k + k * c + m * c) * 4)
    parts = [dense(5, 4, 3), a(3), a(3), dense(4, 5, 3),        # layer 1
             dense(5, 3, 2), a(2), a(2), dense(3, 5, 2), dense(5, 2, 3)]
    w = work.gcn_train_step(cfg, n, nnz)
    assert w.flops == sum(p[0] for p in parts)
    assert w.bytes == sum(p[1] for p in parts)


def test_roofline_reports_its_bound():
    least, bound = work.roofline_seconds(work.Work(10.0, 1.0), 10.0, 10.0)
    assert (least, bound) == (1.0, "flops")
    least, bound = work.roofline_seconds(work.Work(1.0, 30.0), 10.0, 10.0)
    assert (least, bound) == (3.0, "bytes")
    assert math.isfinite(least)


SPMM_TEXT = ("%spmm_sell.{i} = {dt}[8,3]{{1,0}} custom-call(s32[2]{{0}} %a, "
             "f32[2,4,4]{{2,1,0}} %v, {dt}[8,3]{{1,0}} %h), "
             "operand_layout_constraints={{s32[2]{{0}}, f32[2,4,4]{{2,1,0}}, "
             "{dt}[8,3]{{1,0}}}}")
SDDMM_TEXT = ("%sddmm_sell.5 = f32[2,4,4]{2,1,0} custom-call(s32[2]{0} %a, "
              "f32[8,2]{1,0} %b, f32[2,8]{1,0} %c)")


def test_kernel_call_reads_widths_and_dtypes():
    f32 = SPMM_TEXT.format(i=1, dt="f32")
    assert work.kernel_call("spmm_sell", f32, 8, 10) == work.spmm(10, 8, 8, 3)
    bf16 = SPMM_TEXT.format(i=1, dt="bf16")
    assert work.kernel_call("spmm_sell", bf16, 8, 10) == work.spmm(
        10, 8, 8, 3, dense_bytes=2)
    # C taken as [k, n]: both operands give k = 2
    assert work.kernel_call("sddmm_sell", SDDMM_TEXT, 8, 10) == work.sddmm(
        10, 8, 8, 2)
    assert work.kernel_call("spmm_sell", "spmm_sell.1", 8, 10) is None
    assert work.planned_kernel("spmm", "sell", "relu") == "spmm_sell_epilogue"
    assert work.planned_kernel("sddmm", "ell", None) == "sddmm_blockcoo"
    assert work.planned_kernel("spmm", "csr", None) is None


def _roofline(kernels, texts):
    import importlib.util

    path = pathlib.Path(trace.__file__).parent / "metrics" / \
        "sparse_roofline.train.py"
    spec = importlib.util.spec_from_file_location("sparse_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ops = [Event("spmm_sell.1", 10, 30), Event("spmm_sell.2", 40, 50),
           Event("sddmm_sell.5", 60, 70), Event("fusion.1", 70, 90)]
    t = Trace(device_ops={"/device:TPU:0": ops},
              annotations=[Event("bench.window", 0, 100)], op_text=texts)
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e10}
    ctx = {"trace": t, "peaks": peaks, "graph_n": 8, "graph_nnz": 10,
           "kernels": kernels}
    return mod.read(ctx), ctx["notes"]


def test_sparse_roofline_counts_every_call_by_its_own_shapes():
    texts = {"spmm_sell.1": SPMM_TEXT.format(i=1, dt="f32"),
             "spmm_sell.2": SPMM_TEXT.format(i=2, dt="bf16"),
             "sddmm_sell.5": SDDMM_TEXT}
    value, notes = _roofline({"spmm_sell", "sddmm_sell",
                              "spmm_sell_epilogue"}, texts)
    least = sum(work.roofline_seconds(w, 1e12, 1e10)[0] for w in (
        work.spmm(10, 8, 8, 3), work.spmm(10, 8, 8, 3, dense_bytes=2),
        work.sddmm(10, 8, 8, 2)))
    assert value == pytest.approx(100.0 * least / 40e-9)
    assert notes == ["sparse_roofline.train: spmm_sell_epilogue planned, "
                     "no call in the trace (its result unused)"]


def test_sparse_roofline_unplanned_or_unread_kernel_reports_nothing():
    texts = {"spmm_sell.1": SPMM_TEXT.format(i=1, dt="f32"),
             "spmm_sell.2": SPMM_TEXT.format(i=2, dt="f32"),
             "sddmm_sell.5": SDDMM_TEXT}
    value, notes = _roofline({"spmm_sell"}, texts)
    assert value is None and "sddmm_sell.5 ran" in notes[-1]
    value, notes = _roofline({"spmm_sell", "sddmm_sell"},
                             dict(texts, **{"spmm_sell.2": "spmm_sell.2"}))
    assert value is None and "shapes of spmm_sell.2" in notes[-1]
