"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

A trace is read into :class:`Trace` (device op intervals plus host
annotations) by :func:`load`; everything else works on that plain
structure, so tests can build one by hand.  All times are nanoseconds
as the profiler reports them.  On a v5e the device plane's clock was
seen to run 1-2 ms behind the host's (ops ending before the host call
that launched them began): busy time and idle share are exact to that
at the window's two edges, and the attribution of an idle gap to a host
annotation only to that precision.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_ANNOTATION = "bench.window"
ANNOTATION_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """Device ops per device plane, and the benchmark's host annotations;
    ``op_text`` maps a device op's name to its whole HLO text, operand
    and result shapes included."""

    device_ops: Dict[str, List[Event]]
    annotations: List[Event]
    op_text: Dict[str, str] = dataclasses.field(default_factory=dict)

    def window(self) -> Tuple[float, float]:
        """The traced window: the span of the ``bench.window``
        annotation(s)."""
        wins = [a for a in self.annotations if a.name == WINDOW_ANNOTATION]
        if not wins:
            raise ValueError("trace has no bench.window annotation")
        return min(a.start_ns for a in wins), max(a.end_ns for a in wins)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event, whose name on a TPU
    is the instruction's whole text (``%spmm_sell.1 = f32[...] ...``); a
    Pallas kernel's instruction carries the ``name=`` it was given."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    annotations: List[Event] = []
    op_text: Dict[str, str] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = device_ops.setdefault(plane.name, [])
                for e in line.events:
                    name = op_name(e.name)
                    op_text.setdefault(name, e.name)
                    ops.append(Event(name, e.start_ns, e.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations.extend(
                    Event(e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX))
    return Trace(device_ops=device_ops, annotations=annotations,
                 op_text=op_text)


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals covered by ``events``."""
    merged: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [(s, t) for s, t in merged]


def busy_ns(trace: Trace) -> float:
    """Device busy time inside the window, averaged over device planes
    that ran anything."""
    lo, hi = trace.window()
    per_device = [sum(t - s for s, t in union(_clip(ops, lo, hi)))
                  for ops in trace.device_ops.values()]
    per_device = [b for b in per_device if b > 0]
    return sum(per_device) / len(per_device) if per_device else 0.0


def op_totals(trace: Trace, clip: bool = True
              ) -> Dict[str, Tuple[int, float]]:
    """(count, total ns) per device op name inside the window, summed
    over devices.  ``clip=False`` takes every op that overlaps the
    window whole, so that its time and its work stay paired."""
    lo, hi = trace.window()
    out: Dict[str, Tuple[int, float]] = {}
    for ops in trace.device_ops.values():
        inside = (_clip(ops, lo, hi) if clip else
                  [e for e in ops if e.end_ns > lo and e.start_ns < hi])
        for e in inside:
            n, t = out.get(e.name, (0, 0.0))
            out[e.name] = (n + 1, t + e.dur_ns)
    return out


def base_name(name: str) -> str:
    """An op's name without the numeric suffix XLA gives each copy of an
    instruction (``spmm_sell.3`` -> ``spmm_sell``)."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def idle_gaps(trace: Trace, device: Optional[str] = None
              ) -> List[Tuple[float, float]]:
    """Intervals of the window in which the device ran nothing."""
    lo, hi = trace.window()
    planes = [device] if device else [
        d for d, ops in sorted(trace.device_ops.items()) if ops]
    if not planes:
        return [(lo, hi)]
    gaps = []
    cursor = lo
    for s, t in union(_clip(trace.device_ops[planes[0]], lo, hi)):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, t)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def gaps_by_annotation(trace: Trace) -> Dict[str, float]:
    """Idle ns of the first device, each gap given to the ``bench.*``
    host annotation that overlaps it most (``host:unannotated`` where
    none does)."""
    anns = sorted((a for a in trace.annotations
                   if a.name != WINDOW_ANNOTATION), key=lambda a: a.start_ns)
    starts = [a.start_ns for a in anns]
    longest = max((a.dur_ns for a in anns), default=0.0)
    out: Dict[str, float] = {}
    for s, t in idle_gaps(trace):
        best, best_overlap = "host:unannotated", 0.0
        # only annotations starting in [s - longest, t) can overlap
        for a in anns[bisect.bisect_left(starts, s - longest):
                      bisect.bisect_left(starts, t)]:
            ov = min(t, a.end_ns) - max(s, a.start_ns)
            if ov > best_overlap:
                best, best_overlap = a.name, ov
        out[best] = out.get(best, 0.0) + (t - s)
    return out


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a traced run's result line, in seconds."""
    ops = sorted(op_totals(trace).items(), key=lambda kv: -kv[1][1])[:top]
    gaps = sorted(gaps_by_annotation(trace).items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[name, t / 1e9] for name, (_, t) in ops],
            "idle_gaps": [[name, t / 1e9] for name, t in gaps]}


def idle_share_pct(trace: Optional[Trace]) -> Optional[float]:
    """Share of the window in which the device ran nothing, in %."""
    if trace is None or not any(trace.device_ops.values()):
        return None
    lo, hi = trace.window()
    return 100.0 * (1.0 - busy_ns(trace) / (hi - lo))
