"""Device time by program layer, from the scope names every device op
carries.

The program names its layers with ``jax.named_scope`` (``sparse.*`` and
``gnn.*``; the taxonomy is in PERF.md, section 3), and XLA keeps the
JAX ``op_name`` path of each op's root instruction in the op's event
metadata as the ``tf_op`` stat, beside the ``program_id`` of its
executable.  ``jax.profiler.ProfileData`` does not expose metadata
stats, so :func:`load` decodes the ``.xplane.pb`` (an XSpace protocol
buffer) itself: the device planes' "XLA Ops" events, and the host
planes' annotations and ``obs.span`` spans.  :func:`reduce` gives each
op, clipped to ``bench.window``, to one layer by the innermost scope in
its path, and each idle gap to the innermost host span over it.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Tuple

from bench import trace
from bench.trace import Event

SCOPE_PREFIXES = ("sparse.", "gnn.")
HOST_PREFIXES = ("bench.", "serve.", "train.", "sparse.", "gnn.")
UNATTRIBUTED = "unattributed"
LAYERS = ("layout", "xla", "kernel", "vjp", "dispatch", "model",
          UNATTRIBUTED)


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op: its HLO instruction name, the executable it ran
    in, and its ``tf_op`` (empty where XLA gives none, as for copies)."""

    name: str
    program_id: int
    tf_op: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Scoped:
    """Device ops per device plane, and host spans by name."""

    device_ops: Dict[str, List[Op]]
    spans: List[Event]

    def window(self) -> Tuple[float, float]:
        return trace.Trace({}, self.spans).window()


# -- the XSpace wire format ---------------------------------------------------


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: an int for a
    varint, bytes for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:  # fixed64 / fixed32
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _map_entries(entries: List[bytes]) -> Dict[int, bytes]:
    out = {}
    for entry in entries:
        kv = dict(_fields(entry))
        out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def _metadata(raw: bytes, stat_names: Dict[int, str]) -> Tuple[str, dict]:
    """(name, {stat name: value}) of one XEventMetadata."""
    name, stats = "", {}
    for f, v in _fields(raw):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 5:
            key, value = None, None
            for sf, sv in _fields(v):
                if sf == 1:
                    key = sv
                elif sf in (3, 4):  # uint64 / int64
                    value = sv
                elif sf == 5:  # str
                    value = sv.decode("utf-8", "replace")
                elif sf == 7:  # ref: a stat metadata's name
                    value = stat_names.get(sv, "")
            stats[stat_names.get(key, "")] = value
    return name, stats


def _events(line: bytes):
    """(name, metadata id, start ns, end ns) of each event of an XLine."""
    name, t0_ns, events = "", 0, []
    for f, v in _fields(line):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 3:
            t0_ns = v
        elif f == 4:
            events.append(v)
    for raw in events:
        e = dict(_fields(raw))
        start = t0_ns + e.get(2, 0) / 1e3
        yield name, e.get(1, 0), start, start + e.get(3, 0) / 1e3


def load(path: str) -> Scoped:
    """Decode an ``.xplane.pb`` written by ``jax.profiler``."""
    with open(path, "rb") as f:
        space = f.read()
    device_ops: Dict[str, List[Op]] = {}
    spans: List[Event] = []
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, ev_meta, stat_meta = "", [], [], []
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 3:
                lines.append(v)
            elif f == 4:
                ev_meta.append(v)
            elif f == 5:
                stat_meta.append(v)
        device = name.startswith(trace.DEVICE_PLANE_PREFIX)
        if not (device or name.startswith("/host:")):
            continue
        stat_names = {k: dict(_fields(v)).get(2, b"").decode()
                      for k, v in _map_entries(stat_meta).items()}
        meta = {k: _metadata(v, stat_names)
                for k, v in _map_entries(ev_meta).items()}
        for line in lines:
            for line_name, mid, s, t in _events(line):
                text, stats = meta.get(mid, ("", {}))
                if device and line_name == trace.OPS_LINE:
                    device_ops.setdefault(name, []).append(Op(
                        trace.op_name(text), stats.get("program_id") or 0,
                        stats.get("tf_op") or "", s, t))
                elif not device and text.startswith(HOST_PREFIXES):
                    spans.append(Event(text, s, t))
    return Scoped(device_ops, spans)


# -- attribution --------------------------------------------------------------


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost ``sparse.*``/``gnn.*`` scope of an op's path, through
    the transform names JAX wraps around a scope
    (``transpose(jvp(gnn.layer0))``); ``tf_op`` is ``<op_name>:<type>``."""
    path = tf_op.rpartition(":")[0] or tf_op
    found = None
    for part in path.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part.startswith(SCOPE_PREFIXES):
            found = part
    return found


def layer_of(scope: Optional[str]) -> str:
    if scope is None:
        return UNATTRIBUTED
    if scope.startswith("gnn."):
        return "model"
    kind = scope.split(".")[1]
    return kind if kind in ("layout", "xla", "kernel", "vjp") else "dispatch"


@dataclasses.dataclass
class Layers:
    """Device seconds of the window by layer, averaged over the device
    planes that ran anything; the same by (layer, program_id); the
    unattributed ops by (name, program_id); idle seconds by host span."""

    window_s: float
    busy_s: float  # union of the op intervals, as bench.trace counts it
    seconds: Dict[str, float]
    by_program: Dict[Tuple[str, int], float]
    unattributed: Dict[Tuple[str, int], float]
    idle: Dict[str, float]
    scoped: bool  # some op in the window carries a scope

    def share_pct(self, layer: str) -> float:
        return 100.0 * self.seconds.get(layer, 0.0) / self.window_s

    def note(self) -> str:
        busy = sum(self.seconds.values())
        attributed = busy - self.seconds.get(UNATTRIBUTED, 0.0)
        parts = []
        for layer in LAYERS:
            if layer in self.seconds:
                progs = ", ".join(
                    f"{p}: {s:.6f}" for (lay, p), s in
                    sorted(self.by_program.items(), key=lambda kv: -kv[1])
                    if lay == layer)
                parts.append(f"{layer} {self.seconds[layer]:.6f} "
                             f"(program {progs})")
        rest = ", ".join(f"{n}@{p} {s:.6f}" for (n, p), s in sorted(
            self.unattributed.items(), key=lambda kv: -kv[1]))
        idle = ", ".join(f"{n} {s:.6f}" for n, s in sorted(
            self.idle.items(), key=lambda kv: -kv[1]))
        share = 100.0 * attributed / busy if busy else 0.0
        return (f"scopes: window {self.window_s:.6f} s, busy "
                f"{self.busy_s:.6f} s, device s by layer: "
                + "; ".join(parts)
                + f"; attributed {share:.3f}% of busy"
                + f"; unattributed ops: {rest or 'none'}"
                + f"; idle s by host span: {idle or 'none'}")


def reduce(scoped: Scoped) -> Optional[Layers]:
    """The window's device time by layer (None with no device op in it)."""
    lo, hi = scoped.window()
    seconds: Dict[str, float] = {}
    by_program: Dict[Tuple[str, int], float] = {}
    unattributed: Dict[Tuple[str, int], float] = {}
    planes, busy, found = 0, 0.0, False
    for ops in scoped.device_ops.values():
        inside = [(op, min(op.end_ns, hi) - max(op.start_ns, lo))
                  for op in ops]
        inside = [(op, ns) for op, ns in inside if ns > 0]
        planes += bool(inside)
        busy += sum(t - s for s, t in trace.union(
            [Event(op.name, max(op.start_ns, lo), min(op.end_ns, hi))
             for op, _ in inside]))
        for op, ns in inside:
            scope = scope_of(op.tf_op)
            found = found or scope is not None
            layer = layer_of(scope)
            s = ns / 1e9
            seconds[layer] = seconds.get(layer, 0.0) + s
            key = (layer, op.program_id)
            by_program[key] = by_program.get(key, 0.0) + s
            if layer == UNATTRIBUTED:
                key = (op.name, op.program_id)
                unattributed[key] = unattributed.get(key, 0.0) + s
    if not planes:
        return None
    scale = 1.0 / planes
    return Layers(
        window_s=(hi - lo) / 1e9,
        busy_s=busy * scale / 1e9,
        seconds={k: v * scale for k, v in seconds.items()},
        by_program={k: v * scale for k, v in by_program.items()},
        unattributed={k: v * scale for k, v in unattributed.items()},
        idle={k: v / 1e9 for k, v in idle_by_span(scoped).items()},
        scoped=found)


def idle_by_span(scoped: Scoped) -> Dict[str, float]:
    """Idle ns of the first device plane that ran anything, each piece
    of a gap given to the innermost (shortest) host span covering it,
    ``host:unannotated`` where none does."""
    lo, hi = scoped.window()
    planes = [d for d, ops in sorted(scoped.device_ops.items()) if ops]
    ops = scoped.device_ops[planes[0]] if planes else []
    as_events = [Event(o.name, o.start_ns, o.end_ns) for o in ops]
    tr = trace.Trace({"d": as_events} if ops else {}, scoped.spans)
    spans = sorted((s for s in scoped.spans
                    if s.name != trace.WINDOW_ANNOTATION),
                   key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    longest = max((s.dur_ns for s in spans), default=0.0)
    out: Dict[str, float] = {}
    for a, b in trace.idle_gaps(tr):
        near = spans[bisect.bisect_left(starts, a - longest):
                     bisect.bisect_left(starts, b)]
        near = [s for s in near if s.end_ns > a]
        cuts = sorted({a, b} | {t for s in near
                                for t in (s.start_ns, s.end_ns)
                                if a < t < b})
        for s0, s1 in zip(cuts, cuts[1:]):
            over = [s for s in near if s.start_ns <= s0 and s.end_ns >= s1]
            name = min(over, key=lambda s: s.dur_ns).name if over \
                else "host:unannotated"
            out[name] = out.get(name, 0.0) + (s1 - s0)
    return out


# -- what the readers share ---------------------------------------------------


def layers(ctx: dict) -> Optional[Layers]:
    """The traced window's reduction, decoded once per run and kept in
    ``ctx``; its note goes with the run's notes.  None without a trace,
    and where no device op in the window carries a scope."""
    if ctx.get("trace") is None:
        return None
    if "scope_layers" not in ctx:
        ctx["scope_layers"] = _layers(ctx.setdefault("notes", []))
    return ctx["scope_layers"]


def _layers(notes: List[str]) -> Optional[Layers]:
    from bench import harness

    try:
        path = trace.find_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    got = reduce(load(path))
    if got is None:
        return None
    if not got.scoped:
        notes.append("scopes: no device op in the window carries a "
                     "sparse.* or gnn.* scope; layer shares not reported")
        return None
    notes.append(got.note())
    return got


def share(ctx: dict, layer: str) -> Optional[float]:
    got = layers(ctx)
    return None if got is None else got.share_pct(layer)
