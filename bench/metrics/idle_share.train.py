"""Device idle share of the traced window: 1 - (union of device-op
intervals) / window, from the profiler trace."""
from bench import trace


def read(ctx):
    return trace.idle_share_pct(ctx.get("trace"))
