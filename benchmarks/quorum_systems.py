"""Beyond-paper: the §6 closing remark made runnable — score *alternative
quorum systems* (grid, weighted voting) against the paper's cardinality
configurations on one cluster, in one compile, through the declarative
``repro.api.Experiment`` layer.

The paper closes by noting that relaxed intersection (Eqs. 11-14) lets Fast
Paxos adopt quorum systems "not based solely on quorum cardinality" to trade
performance against fault-tolerance.  This benchmark walks that design
space for n = 11:

  card.headline      (q1, q2c, q2f) = (9, 3, 7) — the paper's §5 example
  card.fast_paxos    (6, 6, 9) — Fast Paxos' own three-quarters suggestion
  card.majority      majority fast quorums (q1 = 11 extreme)
  grid.3x3           3x3 grid (§6 construction) embedded in the 11-node
                     cluster: fast = two full rows, classic = one column
  weighted           Gifford-style weighted voting, three heavy acceptors

All five go into ONE ``Experiment``; its mask-table lowering (the single
quorum lowering, DESIGN.md §2) scores them with ONE ``fast_path`` compile
plus ONE ``race`` compile (asserted via ``engine.TRACE_COUNTS``).  The
cardinality rows are then re-run as their own all-cardinality experiment —
which lowers to the k-th-order-statistic specialization — and asserted
bit-identical: the differential anchor that licenses the general masked
path.  Axes reported per system: fast-path p50/p99, P(recovery | race), and
brute-force crash tolerance per phase; plus a fault-injection coda (a grid
row outage vs the same crash count scattered) showing why *placement* starts
to matter once quorums have structure.

Usage:  PYTHONPATH=src python -m benchmarks.quorum_systems [--smoke]
"""
from __future__ import annotations

import argparse
from typing import List, Tuple

import jax

from repro.api import Experiment, Workload
from repro.core.quorum import ExplicitQuorumSystem, QuorumSpec
from repro.montecarlo import build_mask_table, engine
from repro.montecarlo.scenarios import grid_wan, weighted_acceptors

N = 11
SAMPLES = 50_000
DELTA_MS = 0.2


def systems() -> List[Tuple[str, object]]:
    """(name, masks) for every scored family; all masks share n = 11."""
    grid = ExplicitQuorumSystem.grid(3).to_masks().embed(N)
    _, weighted = weighted_acceptors()          # default 3-heavy weighting
    return [
        ("card.headline", QuorumSpec.paper_headline(N).to_masks()),
        ("card.fast_paxos", QuorumSpec.fast_paxos(N).to_masks()),
        ("card.majority", QuorumSpec.majority_fast(N).to_masks()),
        ("grid.3x3", grid),
        ("weighted.3heavy", weighted),
    ]


def run(quick: bool = False, seed: int = 0):
    samples = 4_000 if quick else SAMPLES
    named = systems()
    cards = [QuorumSpec.paper_headline(N), QuorumSpec.fast_paxos(N),
             QuorumSpec.majority_fast(N)]
    rows: List[Tuple[str, float]] = [("qsys.n_systems", len(named))]

    # -- one declared experiment, two workloads, two engine calls (one
    # compile each): the whole mixed-family table per call
    exp = Experiment(systems=[m for _, m in named],
                     workload=Workload.conflict_free(),
                     samples=samples, seed=seed)
    t0 = dict(engine.TRACE_COUNTS)
    fast = exp.run("montecarlo")
    race = Experiment(systems=exp.systems,
                      workload=Workload.race(k=2, delta_ms=DELTA_MS),
                      samples=samples, seed=seed).run("montecarlo")
    traces = (engine.TRACE_COUNTS["fast_path"] - t0["fast_path"],
              engine.TRACE_COUNTS["race"] - t0["race"])
    assert traces[0] <= 1 and traces[1] <= 1, (
        f"per-system re-jit crept back in: {traces} traces for "
        f"{len(named)} quorum systems")
    rows.append(("qsys.engine_compiles", sum(traces)))

    # -- differential anchor: the cardinality rows re-declared as their own
    # all-cardinality experiment lower to the "q" (k-th-order-statistic)
    # specialization, and must be bit-identical under the same seed (common
    # random numbers) — the parity that licenses the general masked path.
    fast_q = Experiment(systems=cards, workload=Workload.conflict_free(),
                        samples=samples, seed=seed).run("montecarlo")
    race_q = Experiment(systems=cards,
                        workload=Workload.race(k=2, delta_ms=DELTA_MS),
                        samples=samples, seed=seed).run("montecarlo")
    assert "q" in engine.build_mask_table(cards), \
        "all-cardinality batch must carry the kth-gather specialization"
    assert bool((fast.raw["latency_ms"][: len(cards)]
                 == fast_q.raw["latency_ms"]).all()), \
        "masked fast path diverged from cardinality specialization"
    for k in race_q.raw:
        assert bool((race.raw[k][: len(cards)] == race_q.raw[k]).all()), (
            f"masked race output {k!r} diverged from cardinality "
            f"specialization")
    rows.append(("qsys.masked_matches_threshold_bitwise", len(cards)))

    # -- per-system frontier rows
    for i, (name, _) in enumerate(named):
        ft = fast.fault_tolerance[i]
        rows.append((f"qsys.[{name}].fast_p50_ms",
                     float(fast.summary["p50_ms"][i])))
        rows.append((f"qsys.[{name}].fast_p99_ms",
                     float(fast.summary["p99_ms"][i])))
        rows.append((f"qsys.[{name}].p_recovery",
                     float(race.summary["recovery_rate"][i])))
        rows.append((f"qsys.[{name}].ft_fast", ft["phase2_fast"]))
        rows.append((f"qsys.[{name}].ft_classic", ft["phase2_classic"]))
        rows.append((f"qsys.[{name}].ft_phase1", ft["phase1"]))

    # -- fault-injection coda: with structured quorums, *which* acceptors
    # fail matters, not just how many.  A full grid-row outage (one WAN
    # region down) leaves a fast quorum intact; the same three crashes
    # scattered one-per-row break every fast AND phase-1 quorum.
    inj_samples = min(samples, 4_000)
    kk = jax.random.PRNGKey(seed + 1)
    undecided = {}
    for tag, crashed in (("row_outage", (3, 4, 5)),
                         ("scattered", (0, 4, 8))):
        scen, masks = grid_wan(cols=3, k=2, delta_ms=DELTA_MS,
                               crashed=crashed)
        out = scen.with_spec(samples=inj_samples).run(
            kk, build_mask_table([masks]))
        undecided[tag] = float(out["undecided"].mean())
        rows.append((f"qsys.grid_wan.{tag}.undecided_rate", undecided[tag]))
        rows.append((f"qsys.grid_wan.{tag}.p_recovery",
                     float(out["recovery"].mean())))
    # a row outage also takes out every phase-1 quorum (each column crosses
    # the dead row), so recovery is off — but the surviving row pair still
    # fast-commits the large majority of instances, whereas the scattered
    # crash set leaves no live quorum of any kind.
    assert undecided["scattered"] > 0.99, \
        "scattered 3-crash must break every grid quorum"
    assert undecided["row_outage"] < 0.2, \
        "a single-row outage must leave the grid's fast path mostly live"

    # -- frontier coda: the same five systems through the streamed Pareto
    # scorer (repro.frontier via api.frontier) — which of the §6 families
    # survive dominance once the tail axis is measurable?
    from repro.api import frontier as api_frontier
    from repro.montecarlo.streaming import StreamSettings
    trials = 131_072 if quick else 2_000_000
    fr = api_frontier([m for _, m in named], trials=trials,
                      stream=StreamSettings(chunk=16_384), seed=seed)
    rows.append(("qsys.frontier.n_systems", len(fr.labels)))
    rows.append(("qsys.frontier.n_members", len(fr.frontier_indices)))
    for (name, _), lab in zip(named, fr.labels):
        rows.append((f"qsys.[{name}].on_frontier",
                     float(fr.row(lab)["on_frontier"])))
    # the paper's headline point trades tail latency against phase-1
    # fault tolerance in a way nothing in this batch dominates
    assert fr.row(fr.labels[0])["on_frontier"], fr.table(False)

    return rows


def main(quick: bool = False):
    rows = run(quick)
    for name, val in rows:
        print(f"{name},{val:.6g}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sample count; asserts only")
    args = ap.parse_args()
    main(quick=args.smoke)
