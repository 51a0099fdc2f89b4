"""Plan quorum systems instead of sweeping them (DESIGN.md §11).

Successive-halving search over the n=11 cardinality space: score all 271
valid systems cheaply, prune what is dominated beyond the cheap rung's
noise margin, spend the full budget only on the survivors — same Pareto
frontier as the exhaustive sweep, a fraction of the trials.  Repeat
questions are answered from warm state (cached search, memoized scores,
zero new engine compiles).

Run:  PYTHONPATH=src python examples/planner_quickstart.py
"""
import time

from repro.api import Experiment, Workload, plan
from repro.core.quorum import QuorumSpec
from repro.montecarlo.streaming import StreamSettings
from repro.planner import Planner, PlannerServer, query_server

# One-call front door: search the family, filter the frontier for the
# fault budget, rank by the objective.  (10^5 final trials keeps this
# example quick; the planner defaults to 10^6.)
t0 = time.perf_counter()
r = plan(n=11, family="cardinality", trials=100_000,
         faults={"classic": 1},           # must survive 1 classic-path crash
         objective="race_p999_ms")        # cheapest contended tail
print(f"[plan] {r.recommended}  (q1={r.system['q1']}, "
      f"q2c={r.system['q2c']}, q2f={r.system['q2f']})")
print(f"[plan] fast p50 {r.predicted_ms['fast_p50']:.2f}ms, "
      f"race p99.9 {r.predicted_ms['race_p999']:.2f}ms, "
      f"crash budget {r.fault_tolerance}")
print(f"[plan] scored {r.search['budget_fraction']:.0%} of the exhaustive "
      f"trial budget in {time.perf_counter() - t0:.1f}s "
      f"({r.engine_compiles} engine compiles)")

# Same geometry, different question: answered from the cached search —
# no new search, no new compiles, milliseconds.
t0 = time.perf_counter()
r2 = plan(n=11, family="cardinality", trials=100_000,
          faults={"fast": 1, "phase1": 1}, objective="fast_p50_ms")
print(f"[warm] {r2.recommended} in {time.perf_counter() - t0 :.3f}s "
      f"(cold={r2.cold}, compiles={r2.engine_compiles})")

# An Experiment asks under ITS workload and engine knobs (crashed
# acceptors are folded into the scored delay model).
exp = Experiment(systems=[QuorumSpec.paper_headline(11)],
                 workload=Workload.race(k=3, delta_ms=0.5),
                 stream=StreamSettings(shard=False))
r3 = exp.plan(faults={"classic": 2}, trials=100_000)
print(f"[exp]  3-way race @0.5ms, classic>=2: {r3.recommended}")

# As a persistent service: concurrent queries that differ only in fault
# budget / objective batch into ONE search.  (CLI equivalent:
#   python -m repro.planner serve &  /  python -m repro.planner query)
srv = PlannerServer(planner=Planner(), port=0, batch_window_s=0.02)
srv.start()
try:
    q = dict(op="plan", n=11, family="cardinality", trials=100_000)
    a = query_server(dict(q, faults={"classic": 1}), port=srv.port)
    b = query_server(dict(q, faults={"classic": 1}), port=srv.port)
    print(f"[serve] {a['recommended']} on :{srv.port}; repeat query "
          f"cold={b['cold']}, compiles={b['engine_compiles']}")
finally:
    srv.shutdown()
