"""repro.planner: successive-halving quorum search and a persistent
search-and-serve planner (DESIGN.md §11).

Three layers, importable separately:

  search    plain-data rung schedules + margin-dominance pruning +
            the ``successive_halving`` loop (no JAX in the control flow)
  cache     ``EngineCache`` — warm compiled-engine pool with a
            content-fingerprint result memo
  service   ``Planner`` (in-process), ``PlannerServer`` (JSON lines over
            TCP, batched by geometry), ``query_server`` client

CLI: ``python -m repro.planner serve | query | plan``.
"""
from .cache import DEFAULT_STREAM, EngineCache, trace_total
from .search import (Rung, RungReport, SearchResult, default_schedule,
                     prune_survivors, search, successive_halving)
from .service import (PlanQuery, PlanResult, Planner, PlannerServer,
                      query_server, resolve_workload)

__all__ = [
    "DEFAULT_STREAM", "EngineCache", "trace_total",
    "Rung", "RungReport", "SearchResult", "default_schedule",
    "prune_survivors", "search", "successive_halving",
    "PlanQuery", "PlanResult", "Planner", "PlannerServer",
    "query_server", "resolve_workload",
]
