"""The XSpace decoder and the attribution of device time to program
layers, on recorded v5e traces and on hand-built ones."""
import importlib.util
import pathlib
import shutil

import pytest

from bench import harness, scopes, trace
from bench.scopes import Op, Scoped
from bench.trace import Event

DATA = pathlib.Path(__file__).parent / "data"
UNSCOPED = DATA / "v5e_sell_spmm.xplane.pb"
SCOPED = DATA / "v5e_scoped_gcn_step.xplane.pb"
READERS = ["layout_share.train", "xla_path_share.train", "vjp_share.train",
           "graph_build_s.train"]


def _reader(name):
    path = pathlib.Path(scopes.__file__).parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_decoder_reads_tf_op_and_program_id():
    """The recorded SELL SpMM (see test_trace_work): the tile-value
    gather and the kernel, as XLA named them before any scope existed."""
    got = scopes.load(str(UNSCOPED))
    ops = {o.name: o for o in got.device_ops["/device:TPU:0"]}
    assert ops["fusion"].tf_op == "jit(<lambda>)/gather:"
    assert ops["spmm_sell.1"].tf_op == (
        "jit(<lambda>)/jit(spmm_sell_kernel)/spmm_sell/pallas_call:")
    assert ops["fusion"].program_id == ops["spmm_sell.1"].program_id \
        == 14012064607135173214
    # async copies carry no tf_op
    assert ops["copy-start"].tf_op == ""
    # the same events, at the same times, as jax.profiler reads them
    # (which truncates each to whole nanoseconds)
    ref = trace.load(str(UNSCOPED)).device_ops["/device:TPU:0"]
    mine = got.device_ops["/device:TPU:0"]
    assert [o.name for o in mine] == [e.name for e in ref]
    assert all(abs(o.start_ns - e.start_ns) <= 2
               and abs(o.end_ns - e.end_ns) <= 2 for o, e in zip(mine, ref))
    assert [s.name for s in got.spans] == ["bench.step", "bench.step"]


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(step)/jvp(gnn.layer2)/sparse.spmm.sell/sparse.layout.tile_values"
     "/gather:", "sparse.layout.tile_values"),
    ("jit(loss)/transpose(jvp(sparse.vjp.spmm))/scatter-add:",
     "sparse.vjp.spmm"),
    ("jit(step)/transpose(jvp(gnn.layer0))/dot_general:", "gnn.layer0"),
    ("jit(step)/jvp(gnn.layer0)/sparse.spmm.sell/jit(spmm_sell_kernel)/"
     "sparse.kernel.spmm_sell/spmm_sell/pallas_call:",
     "sparse.kernel.spmm_sell"),
    ("jit(<lambda>)/gather:", None),
    ("", None),
])
def test_innermost_scope_through_transforms(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_layer_of_each_scope():
    assert [scopes.layer_of(s) for s in (
        "sparse.layout.pad", "sparse.xla.spmm_elements",
        "sparse.kernel.sddmm_sell", "sparse.vjp.attention",
        "sparse.attention.sell", "gnn.scores", None)] == [
        "layout", "xla", "kernel", "vjp", "dispatch", "model",
        "unattributed"]


def _hand_built():
    """Window 0..100 ns; program 1 runs one op of each layer between 10
    and 62, program 2's ``fusion.2`` (a name program 1 also has, with no
    scope) runs 90..120 and is clipped at 100.  Idle: 0..10 and 62..90,
    under nested host spans."""
    step = "jit(step)/"
    ops = [
        Op("fusion.2", 1, step + "jvp(gnn.layer2)/sparse.spmm.sell/"
           "sparse.layout.tile_values/gather:", 10, 40),
        Op("spmm_sell.1", 1, step + "jvp(gnn.layer2)/sparse.spmm.sell/"
           "jit(spmm_sell_kernel)/sparse.kernel.spmm_sell/spmm_sell/"
           "pallas_call:", 40, 50),
        Op("fusion.7", 1, step + "transpose(jvp(gnn.layer2))/"
           "sparse.vjp.spmm/sparse.spmm.sell/sparse.xla.spmm_elements/"
           "scatter-add:", 50, 55),
        Op("fusion.9", 1, step + "transpose(jvp(gnn.layer0))/"
           "sparse.vjp.spmm/mul:", 55, 57),
        Op("fusion.3", 1, step + "jvp(gnn.layer0)/sparse.spmm.sell/add:",
           57, 58),
        Op("fusion.4", 1, step + "transpose(jvp(gnn.layer0))/dot_general:",
           58, 60),
        Op("copy.5", 1, "", 60, 62),
        Op("fusion.2", 2, "jit(loss)/log_softmax:", 90, 120),
    ]
    spans = [Event("bench.window", 0, 100), Event("bench.step", 0, 70),
             Event("serve.compose", 62, 66),
             Event("bench.readback", 70, 100),
             Event("sparse.pack.ell", 75, 80)]
    return Scoped({"/device:TPU:0": ops}, spans)


def test_layers_of_a_hand_built_trace():
    got = scopes.reduce(_hand_built())
    ns = {k: v * 1e9 for k, v in got.seconds.items()}
    assert ns == pytest.approx({"layout": 30, "kernel": 10, "xla": 5,
                                "vjp": 2, "dispatch": 1, "model": 2,
                                "unattributed": 2 + 10})
    assert got.window_s == pytest.approx(100e-9)
    assert got.busy_s == pytest.approx(52e-9 + 10e-9)
    assert got.share_pct("layout") == pytest.approx(30.0)
    assert got.share_pct("vjp") == pytest.approx(2.0)
    # repeated names of two programs stay apart
    un = {k: v * 1e9 for k, v in got.unattributed.items()}
    assert un == pytest.approx({("copy.5", 1): 2, ("fusion.2", 2): 10})
    assert got.by_program[("unattributed", 2)] * 1e9 == pytest.approx(10)
    # each piece of a gap goes to the innermost span over it
    idle = {k: v * 1e9 for k, v in got.idle.items()}
    assert idle == pytest.approx({"bench.step": 10 + 4, "serve.compose": 4,
                                  "bench.readback": 5 + 10,
                                  "sparse.pack.ell": 5})
    assert got.scoped
    note = got.note()
    assert note.startswith("scopes: window ")
    assert "copy.5@1" in note and "fusion.2@2" in note


def test_unscoped_trace_is_not_scoped():
    """A program without scopes (as before they existed) reads nothing."""
    got = scopes.load(str(UNSCOPED))
    ops = got.device_ops["/device:TPU:0"]
    got.spans.append(Event("bench.window", min(o.start_ns for o in ops),
                           max(o.end_ns for o in ops)))
    layers = scopes.reduce(got)
    assert not layers.scoped
    assert set(layers.seconds) == {"unattributed"}


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_trace_reads_nothing(name, tmp_path, monkeypatch):
    read = _reader(name).read
    assert read({}) is None
    assert read({"trace": None}) is None
    # a trace object but no .xplane.pb where the harness writes it
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    from repro import obs

    monkeypatch.setattr(obs, "TRACER", obs.Tracer())
    t = trace.Trace({}, [Event("bench.window", 0, 1)])
    assert read({"trace": t}) is None


def test_graph_build_reads_the_setup_span_tree(monkeypatch):
    from repro import obs

    tracer = obs.Tracer()
    monkeypatch.setattr(obs, "TRACER", tracer)
    with tracer.span("gnn.build_graph"):
        with tracer.span("sparse.stats"):
            pass
        with tracer.span("sparse.pack.ell"):
            pass
    t = trace.Trace({"/device:TPU:0": [Event("fusion", 0, 1)]},
                    [Event("bench.window", 0, 1)])
    ctx = {"trace": t}
    value = _reader("graph_build_s.train").read(ctx)
    (root,) = tracer.spans("gnn.build_graph")
    assert value == pytest.approx(root.dur_ms / 1e3)
    (note,) = ctx["notes"]
    assert note.startswith("graph_build_s.train self s: ")
    assert all(n in note for n in ("gnn.build_graph", "sparse.stats",
                                   "sparse.pack.ell"))


def test_scoped_reference_trace(tmp_path, monkeypatch):
    """One GCN training step at 1,024 nodes on a v5e, with the program's
    scopes (recorded by ``record_scoped_step.py``): at least 99% of the
    busy time has a layer, and the readers find it where the harness
    writes traces."""
    got = scopes.reduce(scopes.load(str(SCOPED)))
    busy = sum(got.seconds.values())
    # the ops do not nest: their durations add up to the busy time
    assert 0 < busy == pytest.approx(got.busy_s, rel=1e-6)
    assert got.seconds.get("unattributed", 0.0) <= 0.01 * busy
    assert {"layout", "kernel", "xla", "model"} <= set(got.seconds)
    assert SCOPED.stat().st_size < 1 << 20
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copyfile(SCOPED, where / "host.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    t = trace.load(str(SCOPED))
    ctx = {"trace": t}
    shares = {name: _reader(name).read(ctx) for name in READERS[:3]}
    assert shares["layout_share.train"] == pytest.approx(
        got.share_pct("layout"))
    assert all(0 <= v <= 100 for v in shares.values())
    assert sum(n.startswith("scopes: ") for n in ctx["notes"]) == 1
