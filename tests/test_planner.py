"""Planner subsystem tests (``repro.planner``, DESIGN.md §11).

Four layers:

  * schedule + pruning kernel: plain-data rungs, margin-dominance
    soundness properties (a hypothesis property drives synthetic noisy
    rung scores bounded by the margins and asserts the full-budget
    Pareto set is never pruned), deterministic within-margin tie cases;
  * engine cache: warm-pool keys, zero-compile repeat scoring, memo hits;
  * service: in-process planner + TCP server round trips, geometry
    batching, fault-budget filtering;
  * acceptance: the n=11 successive-halving search finds EXACTLY the
    direct sweep's Pareto set at the same final budget (common random
    numbers make final-rung scores bit-identical per system) while
    scoring <= 40% of the exhaustive trial budget, and a second
    same-geometry ``plan()`` adds zero ``TRACE_COUNTS`` compiles.
"""
import math
from dataclasses import dataclass
from typing import Tuple

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.frontier import cardinality_family, default_axes, pareto_mask
from repro.frontier.score import AXIS_NAMES, score_systems
from repro.montecarlo import engine
from repro.montecarlo.streaming import StreamSettings
from repro.planner import (EngineCache, PlanQuery, Planner, PlannerServer,
                           Rung, default_schedule,
                           prune_survivors, query_server, search,
                           successive_halving)
from repro.planner.search import quantile_margin_cells, rate_margin

# ---------------------------------------------------------------------------
# Shared scoring configs.
# ---------------------------------------------------------------------------

# Small, fast geometry for cache/service tests.
SMALL_STREAM = StreamSettings(chunk=4_096, shard=False)
SMALL = dict(n=7, chunk=4_096, shard=False, seed=0)
SMALL_SCHEDULE = ((2_000, 2.0), (20_000, 2.0))

# The acceptance geometry: PR 5's sweep at --smoke scale (n=11, 10^6
# trials, chunk 16384, 2-way race at delta 0.2, seed 0).
ACC_N = 11
ACC_TRIALS = 1_000_000
ACC_CHUNK = 16_384
ACC_SCHEDULE = (Rung(100_000), Rung(ACC_TRIALS))

_TRUTH = {}


def _truth():
    """The exhaustive n=11 direct frontier at the acceptance budget,
    scored once per test session (module-level memo — the hypothesis
    fallback wrapper passes no fixtures)."""
    if "fr" not in _TRUTH:
        members = cardinality_family(ACC_N)
        _TRUTH["members"] = members
        _TRUTH["fr"] = score_systems(members, n=ACC_N, trials=ACC_TRIALS,
                                     chunk=ACC_CHUNK, shard=False, seed=0)
    return _TRUTH["members"], _TRUTH["fr"]


# ---------------------------------------------------------------------------
# Schedules are plain data.
# ---------------------------------------------------------------------------

def test_default_schedule_geometric_ascending():
    sched = default_schedule(10_000_000)
    assert [r.trials for r in sched] == [10_000, 100_000, 1_000_000,
                                         10_000_000]
    from repro.planner.search import DEFAULT_SLACK
    assert all(r.slack == DEFAULT_SLACK for r in sched)
    assert default_schedule(5_000, min_trials=10_000) == (Rung(5_000),)
    assert [r.trials for r in default_schedule(1_000_000, eta=100)] \
        == [10_000, 1_000_000]


def test_rung_validation():
    with pytest.raises(ValueError):
        Rung(0)
    with pytest.raises(ValueError):
        Rung(100, slack=0.0)
    with pytest.raises(ValueError):
        default_schedule(0)
    with pytest.raises(ValueError):
        default_schedule(100, eta=1)


def test_successive_halving_rejects_bad_schedules():
    with pytest.raises(ValueError):
        successive_halving(["a"], [], lambda m, t: None)
    with pytest.raises(ValueError):
        successive_halving(["a"], [Rung(100), Rung(100)],
                           lambda m, t: None)
    with pytest.raises(ValueError):
        successive_halving([], [Rung(100)], lambda m, t: None)


# ---------------------------------------------------------------------------
# Margin-dominance pruning: deterministic cases.
# ---------------------------------------------------------------------------

# A compact synthetic axis tuple matching the scorer's shape: two relative
# latency axes, one rate axis, one exact maximize axis.
SYN_AXES = default_axes(precision=0.01, trials=ACC_TRIALS)


def _vals(*rows):
    return np.array(rows, np.float64)


def _gamma(eps=0.01):
    return (1.0 + eps) / (1.0 - eps)


def test_prune_within_margin_tie_survives_together():
    """Two systems inside the rung's sketch/noise margin on a stochastic
    axis are indistinguishable there — neither may prune the other, even
    though one is weakly better everywhere."""
    rung = Rung(10_000, slack=2.0)
    m_cells = quantile_margin_cells(2.0, 10_000, 0.5)
    # row 1 is better on fast_p50 by *half* the margin, ties elsewhere
    g = _gamma()
    base = _vals([1.0, 2.0, 0.1, 1, 1, 1],
                 [1.0 * g ** (-m_cells / 2), 2.0, 0.1, 1, 1, 1])
    keep = prune_survivors(base, SYN_AXES, rung)
    assert keep.tolist() == [True, True]


def test_prune_beyond_margin_dominated_is_pruned():
    rung = Rung(10_000, slack=2.0)
    g = _gamma()
    mq = quantile_margin_cells(2.0, 10_000, 0.5)
    mt = quantile_margin_cells(2.0, 10_000, 0.001)
    mr = rate_margin(2.0, 10_000)
    # row 1 beats row 0 beyond the margin on EVERY stochastic axis and
    # ties the exact axes -> row 0 prunable
    worse = [1.0, 2.0, 0.5, 1, 1, 1]
    better = [1.0 * g ** (-(mq + 1)), 2.0 * g ** (-(mt + 1)),
              0.5 - (mr * 1.5), 1, 1, 1]
    keep = prune_survivors(_vals(worse, better), SYN_AXES, rung)
    assert keep.tolist() == [False, True]
    # ...but an exact-axis advantage for row 0 vetoes the prune
    worse_ft = list(worse)
    worse_ft[3] = 2
    keep = prune_survivors(_vals(worse_ft, better), SYN_AXES, rung)
    assert keep.tolist() == [True, True]


def test_prune_exact_duplicates_survive_together():
    """CRN scoring produces bit-exact duplicate rows for structurally
    identical systems; margin dominance is irreflexive so they can never
    prune each other."""
    rung = Rung(1_000, slack=2.0)
    row = [1.5, 3.0, 0.2, 2, 1, 3]
    keep = prune_survivors(_vals(row, row, row), SYN_AXES, rung)
    assert keep.all()


def test_prune_never_decided_ties_cannot_prune():
    """Two systems that never decide (NaN -> -inf) tie at -inf on the
    latency axes; the -inf vs -inf comparison carries no information and
    must neither count as a strict win nor veto other axes."""
    rung = Rung(10_000, slack=2.0)
    nan = float("nan")
    a = [nan, nan, 0.5, 1, 1, 1]
    b = [nan, nan, 0.5, 1, 1, 1]
    keep = prune_survivors(_vals(a, b), SYN_AXES, rung)
    assert keep.tolist() == [True, True]
    # a decided system beats an undecided one beyond any margin on the
    # latency axes; with a rate edge too, the undecided row is pruned
    c = [1.0, 2.0, 0.1, 1, 1, 1]
    keep = prune_survivors(_vals(a, c), SYN_AXES, rung)
    assert keep.tolist() == [False, True]


def test_prune_singleton_and_empty():
    rung = Rung(1_000)
    assert prune_survivors(np.zeros((1, 6)), SYN_AXES, rung).tolist() \
        == [True]
    assert prune_survivors(np.zeros((0, 6)), SYN_AXES, rung).shape == (0,)


# ---------------------------------------------------------------------------
# Pruning soundness property: bounded-noise rung scores never prune a
# member of the full-budget Pareto set.
# ---------------------------------------------------------------------------

@dataclass
class _FakeResult:
    labels: Tuple[str, ...]
    axes: tuple
    values: np.ndarray

    @property
    def mask(self):
        return pareto_mask(self.values, self.axes)

    @property
    def axis_names(self):
        return tuple(a.name for a in self.axes)

    @property
    def frontier_labels(self):
        return tuple(l for l, m in zip(self.labels, self.mask) if m)


def _noisy(truth: np.ndarray, axes, rung: Rung,
           rng: np.random.RandomState) -> np.ndarray:
    """Rung-scale estimates: truth +/- noise bounded so that margin
    dominance at the rung implies >1-final-cell dominance in truth (the
    soundness precondition the margins are sized for)."""
    out = truth.copy()
    for a, ax in enumerate(axes):
        if ax.name in ("fast_p50_ms", "race_p999_ms"):
            tail = 0.5 if ax.name == "fast_p50_ms" else 0.001
            cells = (quantile_margin_cells(rung.slack, rung.trials, tail)
                     - 1.0) / 2.0
            g = (1.0 + ax.eps) / (1.0 - ax.eps)
            u = rng.uniform(-cells, cells, size=truth.shape[0])
            out[:, a] = truth[:, a] * g ** u
        elif ax.name == "p_recovery":
            bound = (rate_margin(rung.slack, rung.trials) - ax.eps) / 2.0
            bound = max(bound, 0.0)
            out[:, a] = truth[:, a] + rng.uniform(-bound, bound,
                                                  size=truth.shape[0])
    return out


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=2, max_value=4))
def test_halving_never_prunes_full_budget_frontier_n11(noise_seed, n_rungs):
    """The ISSUE acceptance property, against REAL n=11 scores: run
    successive halving where each cheap rung sees the true full-budget
    scores perturbed by noise within the rung's margins (the regime the
    margins are sized for), the final rung sees the exact scores — and
    the search's frontier must equal the direct sweep's, every run."""
    members, fr = _truth()
    truth = np.asarray(fr.values, np.float64)
    labels = tuple(fr.labels)
    truth_frontier = set(fr.frontier_labels)
    rng = np.random.RandomState(noise_seed)

    ladder = [ACC_TRIALS // (10 ** k) for k in range(n_rungs - 1, 0, -1)]
    schedule = tuple(Rung(t) for t in ladder) + (Rung(ACC_TRIALS),)
    pruned_log = []

    def scorer(alive, trials):
        idx = [labels.index(m.label) for m in alive]
        vals = (truth[idx] if trials == ACC_TRIALS
                else _noisy(truth[idx], fr.axes, Rung(trials), rng))
        pruned_log.append((trials, len(alive)))
        return _FakeResult(tuple(labels[i] for i in idx), fr.axes, vals)

    result = successive_halving(list(members), schedule, scorer)
    got = set(result.frontier.frontier_labels)
    assert got == truth_frontier, (
        f"noise_seed={noise_seed}, rungs={n_rungs}: "
        f"lost {truth_frontier - got}, gained {got - truth_frontier}")
    # survivors shrink monotonically and every rung scored someone
    counts = [n for _, n in pruned_log]
    assert counts[0] == len(members)
    assert all(a >= b for a, b in zip(counts, counts[1:]))


# ---------------------------------------------------------------------------
# Engine cache.
# ---------------------------------------------------------------------------

def _small_members():
    return cardinality_family(7)


def test_engine_cache_second_same_shape_scores_zero_compiles():
    cache = EngineCache()
    members = _small_members()
    r1 = cache.score(members, trials=30_000, n=7, stream=SMALL_STREAM,
                     seed=0)
    assert r1.engine_compiles > 0             # cold: fast + race traces
    before = dict(engine.TRACE_COUNTS)
    r2 = cache.score(members, trials=30_000, n=7, stream=SMALL_STREAM,
                     seed=0)
    assert engine.TRACE_COUNTS == before      # memo hit: engine untouched
    assert r2.engine_compiles == 0
    assert cache.memo_hits == 1
    np.testing.assert_array_equal(np.asarray(r1.values),
                                  np.asarray(r2.values))
    # different seed: memo miss, but the jit cache stays warm -> zero
    # NEW compiles even though the engine actually runs
    r3 = cache.score(members, trials=30_000, n=7, stream=SMALL_STREAM,
                     seed=1)
    assert r3.engine_compiles == 0
    assert cache.memo_misses == 2
    assert not np.array_equal(np.asarray(r1.values)[:, :2],
                              np.asarray(r3.values)[:, :2])


def test_engine_cache_scores_match_direct():
    """Routing through the cache changes bookkeeping, never values."""
    cache = EngineCache()
    members = _small_members()[:10]
    via = cache.score(members, trials=9_000, n=7, stream=SMALL_STREAM,
                      seed=3)
    direct = score_systems(members, trials=9_000, n=7, chunk=4_096,
                           shard=False, seed=3)
    np.testing.assert_array_equal(np.asarray(via.values),
                                  np.asarray(direct.values))
    assert via.labels == direct.labels


def test_engine_cache_builds_the_table_once_per_query(monkeypatch):
    """A planner query encodes its masks and builds its mask table once,
    and scores that table (a memo hit builds it once more, to look up)."""
    real, built = engine.build_mask_table, []

    def counting(masks, **kw):
        built.append(len(masks))
        return real(masks, **kw)
    monkeypatch.setattr(engine, "build_mask_table", counting)
    cache = EngineCache()
    members = _small_members()[:6]
    cache.score(members, trials=3_000, n=7, stream=SMALL_STREAM, seed=0)
    assert built == [6]
    cache.score(members, trials=3_000, n=7, stream=SMALL_STREAM, seed=0)
    assert built == [6, 6] and cache.memo_hits == 1


# ---------------------------------------------------------------------------
# Search through the real engine (small scale).
# ---------------------------------------------------------------------------

def test_search_small_matches_direct_frontier():
    members = _small_members()
    sr = search(members, final_trials=20_000,
                schedule=(Rung(2_000), Rung(20_000)), n=7,
                stream=SMALL_STREAM, seed=0)
    direct = score_systems(members, trials=20_000, **{
        k: v for k, v in SMALL.items()})
    assert set(sr.frontier_labels) == set(direct.frontier_labels)
    assert 0 < sr.budget_fraction < 1.0
    assert sr.scored_trials < sr.exhaustive_trials
    # final-rung rows are bit-identical to the direct scores (CRN batch
    # independence): compare every surviving system's vector
    dvals = np.asarray(direct.values)
    svals = np.asarray(sr.frontier.values)
    didx = {l: i for i, l in enumerate(direct.labels)}
    for row, label in enumerate(sr.frontier.labels):
        np.testing.assert_array_equal(svals[row], dvals[didx[label]])


# ---------------------------------------------------------------------------
# Planner + service.
# ---------------------------------------------------------------------------

def _small_query(**over):
    q = dict(n=7, family="cardinality", trials=20_000,
             schedule=SMALL_SCHEDULE, chunk=4_096, shard=False, seed=0)
    q.update(over)
    return q


def test_planner_second_same_geometry_plan_zero_compiles():
    planner = Planner()
    r1 = planner.plan(_small_query(faults={"classic": 1}))
    assert r1.ok and r1.cold
    before = dict(engine.TRACE_COUNTS)
    r2 = planner.plan(_small_query(faults={"fast": 1}))
    assert engine.TRACE_COUNTS == before
    assert not r2.cold and r2.engine_compiles == 0
    assert r2.ok
    # recommendation respects the budget it was asked for
    assert r2.fault_tolerance["fast"] >= 1
    assert r1.fault_tolerance["classic"] >= 1


def test_planner_impossible_budget_reports_not_ok():
    planner = Planner()
    r = planner.plan(_small_query(faults={"fast": 7}))
    assert not r.ok and "no frontier system" in r.reason
    assert r.frontier_labels                 # the frontier is still reported


def test_planner_objective_changes_recommendation_ranking():
    planner = Planner()
    r_tail = planner.plan(_small_query(objective="race_p999_ms"))
    r_fast = planner.plan(_small_query(objective="fast_p50_ms"))
    fr_labels = set(r_tail.frontier_labels)
    assert r_fast.recommended in fr_labels
    assert r_tail.recommended in fr_labels
    # both objectives answered from one cached search
    assert planner.search_misses == 1 and planner.search_hits >= 1


def test_plan_group_batches_same_geometry():
    planner = Planner()
    qs = [PlanQuery.from_dict(_small_query(faults={"classic": 1})),
          PlanQuery.from_dict(_small_query(faults={"fast": 1}))]
    rs = planner.plan_group(qs)
    assert len(rs) == 2 and all(r.ok for r in rs)
    assert planner.search_misses == 1        # ONE search for the batch
    with pytest.raises(ValueError):
        planner.plan_group([qs[0],
                            PlanQuery.from_dict(_small_query(seed=5))])


def test_query_validation():
    with pytest.raises(ValueError):
        PlanQuery(objective="p42")
    with pytest.raises(ValueError):
        PlanQuery(faults={"phase9": 1})
    with pytest.raises(ValueError):
        PlanQuery.from_dict({"nope": 1})
    with pytest.raises(ValueError):
        PlanQuery(trials=0)


def test_server_round_trip_batching_and_zero_compile_repeat():
    srv = PlannerServer(port=0, batch_window_s=0.01)
    srv.start()
    try:
        assert query_server({"op": "ping"}, port=srv.port)["ok"]
        q = {"op": "plan", **_small_query(faults={"classic": 1})}
        q["schedule"] = [list(r) for r in SMALL_SCHEDULE]
        r1 = query_server(q, port=srv.port)
        assert r1["ok"] and r1["cold"]
        before = dict(engine.TRACE_COUNTS)
        r2 = query_server(q, port=srv.port)
        assert engine.TRACE_COUNTS == before
        assert r2["ok"] and not r2["cold"] and r2["engine_compiles"] == 0
        assert r2["recommended"] == r1["recommended"]
        stats = query_server({"op": "stats"}, port=srv.port)
        assert stats["ok"] and stats["search_misses"] == 1
        bad = query_server({"op": "plan", "objective": "nope"},
                           port=srv.port)
        assert not bad["ok"] and "objective" in bad["error"]
    finally:
        srv.shutdown()


def test_api_plan_and_experiment_plan():
    from repro.api import Experiment, Workload, plan
    from repro.core.quorum import QuorumSpec

    planner = Planner()
    r = plan(_small_query(faults={"classic": 1}), planner=planner)
    assert r.ok and r.system["type"] == "QuorumSpec"
    assert r.predicted_ms["fast_p50"] > 0
    assert r.predicted_ms["race_p9999"] >= r.predicted_ms["race_p999"]

    exp = Experiment(systems=[QuorumSpec.paper_headline(7)],
                     workload=Workload.race(k=2, delta_ms=0.2),
                     stream=SMALL_STREAM)
    r2 = exp.plan(faults={"classic": 1}, trials=20_000,
                  schedule=SMALL_SCHEDULE, planner=planner)
    assert r2.ok
    # same geometry as the direct query (n=7, default race workload,
    # same knobs) -> answered from the cached search
    assert not r2.cold and r2.engine_compiles == 0


# ---------------------------------------------------------------------------
# launch_local free-port race (satellite): EADDRINUSE retries.
# ---------------------------------------------------------------------------

def test_launch_local_retries_on_eaddrinuse(monkeypatch):
    from repro.parallel import distributed

    calls = []

    def fake_once(n, d, argv, *, env, timeout_s):
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("coordinator: Address already in use "
                               "(EADDRINUSE)")
        return ["ok"] * n

    monkeypatch.setattr(distributed, "_launch_once", fake_once)
    out = distributed.launch_local(2, 1, ["true"])
    assert out == ["ok", "ok"] and len(calls) == 3


def test_launch_local_exhausts_retries(monkeypatch):
    from repro.parallel import distributed

    def always_busy(n, d, argv, *, env, timeout_s):
        raise RuntimeError("bind failed: EADDRINUSE")

    monkeypatch.setattr(distributed, "_launch_once", always_busy)
    with pytest.raises(RuntimeError, match="EADDRINUSE"):
        distributed.launch_local(1, 1, ["true"], port_retries=2)


def test_launch_local_does_not_retry_other_failures(monkeypatch):
    from repro.parallel import distributed

    calls = []

    def fake_once(n, d, argv, *, env, timeout_s):
        calls.append(1)
        raise RuntimeError("worker exploded for unrelated reasons")

    monkeypatch.setattr(distributed, "_launch_once", fake_once)
    with pytest.raises(RuntimeError, match="unrelated"):
        distributed.launch_local(1, 1, ["true"])
    assert len(calls) == 1

    def unsupported(n, d, argv, *, env, timeout_s):
        calls.append(1)
        raise NotImplementedError("no gloo here")

    calls.clear()
    monkeypatch.setattr(distributed, "_launch_once", unsupported)
    with pytest.raises(NotImplementedError):
        distributed.launch_local(1, 1, ["true"])
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# Acceptance: exact n=11 sweep frontier at <= 40% of the exhaustive
# budget; repeat plan() adds zero compiles.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_acceptance_n11_exact_frontier_under_budget():
    members, direct = _truth()
    sr = search(members, final_trials=ACC_TRIALS, schedule=ACC_SCHEDULE,
                n=ACC_N, stream=StreamSettings(chunk=ACC_CHUNK, shard=False),
                seed=0)
    assert set(sr.frontier_labels) == set(direct.frontier_labels), (
        f"search missed {set(direct.frontier_labels) - set(sr.frontier_labels)}"
        f", invented {set(sr.frontier_labels) - set(direct.frontier_labels)}")
    assert sr.budget_fraction <= 0.40, sr.budget_fraction
    # final-rung scores are bit-identical to the direct sweep's rows
    dvals = np.asarray(direct.values)
    svals = np.asarray(sr.frontier.values)
    didx = {l: i for i, l in enumerate(direct.labels)}
    for row, label in enumerate(sr.frontier.labels):
        np.testing.assert_array_equal(svals[row], dvals[didx[label]])


@pytest.mark.slow
def test_acceptance_second_plan_query_zero_compiles():
    planner = Planner()
    sched = tuple((r.trials, r.slack) for r in ACC_SCHEDULE)
    q = dict(n=ACC_N, family="cardinality", trials=ACC_TRIALS,
             schedule=sched, chunk=ACC_CHUNK, shard=False, seed=0)
    r1 = planner.plan(dict(q, faults={"classic": 1}))
    assert r1.ok and r1.cold
    before = dict(engine.TRACE_COUNTS)
    r2 = planner.plan(dict(q, faults={"fast": 1, "phase1": 1}))
    assert engine.TRACE_COUNTS == before, "warm plan() traced the engine"
    assert r2.ok and not r2.cold and r2.engine_compiles == 0
    _, direct = _truth()
    assert set(r1.frontier_labels) == set(direct.frontier_labels)
