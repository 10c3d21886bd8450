"""Per-instance plan memoization for ``SparseMatrix``.

A ``SparseMatrix`` carries one ``PlanCache`` in its static (aux) pytree
metadata.  The first ``A @ H`` for a given (op, width, policy, dtype)
resolves a dispatch ``Plan`` through the cost model / autotune machinery
and memoizes it; every later call with the same key skips re-planning.

The cache is deliberately *neutral* for jit purposes: two caches always
compare equal and hash alike, so the memo never forces a retrace — only
the matrix's shape/format/stats (the rest of the aux tuple) do.

Each cache also keeps its own hit/miss counters, so per-engine reports
(two serving engines in one process) never alias each other; the
process-wide totals are the registry counters
``plan_cache_{hits,misses}_total``.
"""
from __future__ import annotations

from typing import Any, Dict, Hashable, Optional

from repro import obs

HITS, MISSES = "plan_cache_hits_total", "plan_cache_misses_total"


def plan_cache_stats() -> Dict[str, int]:
    """Aggregate plan-cache counters across every SparseMatrix (the
    registry's series; ``obs.reset()`` zeroes them)."""
    return {"hits": int(obs.REGISTRY.total(HITS)),
            "misses": int(obs.REGISTRY.total(MISSES))}


class PlanCache:
    """Mutable (key -> Plan) memo carried in pytree aux metadata.

    Equality/hash are constant so jit cache keys (which compare aux data)
    are insensitive to the memo's identity and contents.
    """

    __slots__ = ("entries", "hits", "misses")

    def __init__(self):
        self.entries: Dict[Hashable, Any] = {}
        self.hits = 0
        self.misses = 0

    def get(self, key: Hashable) -> Optional[Any]:
        plan = self.entries.get(key)
        if plan is None:
            self.misses += 1
            obs.counter(MISSES).inc()
        else:
            self.hits += 1
            obs.counter(HITS).inc()
        return plan

    def put(self, key: Hashable, plan: Any) -> None:
        self.entries[key] = plan

    def stats(self) -> Dict[str, int]:
        """This instance's counters (see ``plan_cache_stats`` for the
        process-wide aggregate)."""
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self.entries)}

    def __len__(self) -> int:
        return len(self.entries)

    def __eq__(self, other) -> bool:
        return isinstance(other, PlanCache)

    def __hash__(self) -> int:
        return 17  # constant; see class docstring

    def __repr__(self) -> str:
        return f"PlanCache({len(self.entries)} plans)"
