"""Seconds of graph set-up: the ``gnn.build_graph`` span this process
recorded (``obs.TRACER``), with the self time of it and of each span
under it (``sparse.stats``, ``sparse.pack.<form>``) in a note.  Reported
with the device metrics of a traced run, so a run whose trace holds no
device op reports it no more than they."""


def self_seconds(spans, roots):
    """(name, self seconds) over the span trees under ``roots``, largest
    first: a span's time less its children's."""
    children = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    out, todo = {}, list(roots)
    while todo:
        s = todo.pop()
        kids = children.get(s.span_id, [])
        own = (s.dur_ms - sum(k.dur_ms for k in kids)) / 1e3
        out[s.name] = out.get(s.name, 0.0) + own
        todo.extend(kids)
    return sorted(out.items(), key=lambda kv: -kv[1])


def read(ctx):
    t = ctx.get("trace")
    if t is None or not any(t.device_ops.values()):
        return None
    from repro import obs

    spans = obs.TRACER.spans()
    roots = [s for s in spans if s.name == "gnn.build_graph"]
    if not roots:
        return None
    ctx.setdefault("notes", []).append(
        "graph_build_s.train self s: " + ", ".join(
            f"{name} {s:.6f}" for name, s in self_seconds(spans, roots)))
    return sum(r.dur_ms for r in roots) / 1e3
