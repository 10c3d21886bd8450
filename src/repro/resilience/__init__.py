"""repro.resilience — fault injection, retry, shedding, recovery.

The layer that turns a fast demo into a system that stays up:

* :mod:`repro.resilience.chaos` — deterministic, seed-driven
  :class:`FaultPlan` injected at named sites across the executor, both
  serving engines, the train loop, the checkpointer, and the DeltaGraph
  repack thread.  Zero overhead when disarmed.
* :mod:`repro.resilience.errors` — the structured error taxonomy
  (poison vs transient vs shed vs deadline vs closed) every engine
  speaks, plus :func:`classify` for the retry decision.
* :mod:`repro.resilience.retry` — exponential backoff with jitter and
  a token-bucket :class:`RetryBudget` so fault storms fail fast instead
  of amplifying load.
* :mod:`repro.resilience.supervisor` — bounded worker-thread restarts
  for the serving loops.

Recovery actions are visible in ``obs.snapshot()`` via
``resilience_retries_total{site,kind}``, ``resilience_shed_total``,
``resilience_quarantined_total{kind}``, ``resilience_degraded_total``,
``resilience_worker_restarts_total{worker}`` and
``resilience_recoveries_total{site}``; injected faults count in
``chaos_faults_total{site,kind}``.
"""
from repro.resilience import chaos
from repro.resilience.chaos import (FaultPlan, FaultSpec,
                                    ProcessKillRequested,
                                    WorkerHangRequested, WorkerKilled)
from repro.resilience.errors import (CompileError, DeadlineExceededError,
                                     EngineClosedError, NaNOutputError,
                                     PoisonRequestError, RequestShedError,
                                     ResilienceError,
                                     TransientExecutorError, WorkerLostError,
                                     classify)
from repro.resilience.retry import RetryBudget, RetryPolicy, call_with_retry
from repro.resilience.supervisor import WorkerSupervisor

__all__ = [
    "CompileError", "DeadlineExceededError", "EngineClosedError",
    "FaultPlan", "FaultSpec",
    "NaNOutputError", "PoisonRequestError", "ProcessKillRequested",
    "RequestShedError", "ResilienceError", "RetryBudget", "RetryPolicy",
    "TransientExecutorError", "WorkerHangRequested", "WorkerKilled",
    "WorkerLostError", "WorkerSupervisor", "call_with_retry", "chaos",
    "classify",
]
