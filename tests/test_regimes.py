"""Scenario-regime tests (``repro.montecarlo.traces`` / ``regimes`` and the
declarative ``Workload`` config API, DESIGN.md §12): trace-replay quantile
fidelity, Markov-chain validation, the single-regime i.i.d. degeneracy
contract, chunk-invariance of regime occupancy, compile discipline,
``Workload``/``Experiment`` config round-trips, and the ``RunSpec``
deprecation shims."""
import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.quorum import QuorumSpec
from repro.montecarlo import build_mask_table, engine, streaming
from repro.montecarlo.regimes import (MarkovRegimes, RegimeStreamSummary,
                                      gray_failure)
from repro.montecarlo.scenarios import RunSpec, k_way_race
from repro.montecarlo.traces import EmpiricalDelay

KEY = jax.random.PRNGKey(0)
FFP = QuorumSpec.paper_headline(11)
TABLE = build_mask_table([FFP])
OFFS = jnp.array([0.0, 0.25], jnp.float32)

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "scenarios")

# Fixed-seed regression anchor — regenerate via tests/regen_anchors.py
# (``regimes()``).  Occupancy and decide counts are integer-exact: any
# drift means the regime key domain, the epoch->trial mapping, or the
# per-regime scatter changed.
REGIME_ANCHOR = {
    'occupancy': [100000, 0, 0],
    'n_fast': 98331,
    'n_recovery': 1669,
    'n_undecided': 0,
    'p50_ms': 1.22746,
}


def _race(key, trials, chunk, regimes=None, k_max="auto"):
    return streaming.race_stream(key, TABLE, OFFS, None, n=11,
                                 k_proposers=2, trials=trials, chunk=chunk,
                                 shard=False, k_max=k_max, regimes=regimes)


# ---------------------------------------------------------------------------
# EmpiricalDelay: trace replay as an inverse-CDF quantile table
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16), size=st.integers(5, 2_000),
       scale=st.floats(0.2, 20.0))
def test_empirical_sampled_quantiles_match_trace(seed, size, scale):
    """Satellite: sampled quantiles land between the model's own quantile
    values one grid step around the target probability (grid resolution
    1/(Q-1) — below the stream sketch's default precision) plus Monte-
    Carlo rank noise at the sample count."""
    rng = np.random.default_rng(seed)
    d = EmpiricalDelay.from_trace(scale * rng.lognormal(0.0, 0.6, size)
                                  + 0.05)
    samp = np.asarray(d.sample_hops(jax.random.PRNGKey(seed), (100_000,)))
    for q in (0.1, 0.5, 0.9, 0.99):
        got = float(np.quantile(samp, q))
        # bracket by the model's quantile function +-1% in probability
        # (grid step 1/255 ~ 0.4%, plus ~3 sigma of rank noise at 1e5)
        lo = float(d.quantile(max(0.0, q - 0.01)))
        hi = float(d.quantile(min(1.0, q + 0.01)))
        assert lo - 1e-3 <= got <= hi + 1e-3, (q, got, lo, hi)


def test_empirical_degenerate_single_point_trace():
    d = EmpiricalDelay.from_trace([0.7])
    samp = np.asarray(d.sample_hops(KEY, (4, 257, 11)))
    assert samp.shape == (4, 257, 11)
    np.testing.assert_allclose(samp, 0.7, rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), size=st.integers(1, 500))
def test_empirical_grid_monotone_by_construction(seed, size):
    """from_trace always yields a strictly increasing prob grid and
    non-decreasing quantile values — validate() accepts its own output."""
    rng = np.random.default_rng(seed)
    d = EmpiricalDelay.from_trace(rng.exponential(2.0, size)).validate()
    p = np.asarray(d.probs)
    v = np.asarray(d.values_ms)
    assert np.all(np.diff(p) > 0) and p[0] == 0.0 and p[-1] == 1.0
    assert np.all(np.diff(v) >= 0)


def test_empirical_rejects_non_monotone_grid():
    good = EmpiricalDelay.from_trace([1.0, 2.0, 3.0, 4.0], n_quantiles=4)
    with pytest.raises(ValueError, match="non-decreasing"):
        EmpiricalDelay(probs=good.probs,
                       values_ms=jnp.array([1.0, 3.0, 2.0, 4.0],
                                           jnp.float32)).validate()
    with pytest.raises(ValueError, match="strictly increasing"):
        EmpiricalDelay(probs=jnp.array([0.0, 0.6, 0.4, 1.0], jnp.float32),
                       values_ms=good.values_ms).validate()
    with pytest.raises(ValueError, match="non-finite"):
        EmpiricalDelay.from_trace([1.0, np.inf])
    with pytest.raises(ValueError, match="negative"):
        EmpiricalDelay.from_trace([1.0, -0.5])


def test_empirical_config_roundtrip_exact():
    from repro.montecarlo.latency import delay_from_config, delay_to_config
    d = EmpiricalDelay.from_trace(
        np.random.default_rng(3).lognormal(0.5, 0.4, 300), n_quantiles=64)
    cfg = json.loads(json.dumps(delay_to_config(d)))
    d2 = delay_from_config(cfg)
    np.testing.assert_array_equal(np.asarray(d.probs, np.float32),
                                  np.asarray(d2.probs, np.float32))
    np.testing.assert_array_equal(np.asarray(d.values_ms, np.float32),
                                  np.asarray(d2.values_ms, np.float32))
    # raw-trace form fits at load time
    d3 = delay_from_config({"kind": "empirical",
                            "trace_ms": [0.3, 0.4, 0.5], "n_quantiles": 8})
    assert np.asarray(d3.probs).shape == (8,)


def test_empirical_composes_with_wrappers_in_stream():
    from repro.montecarlo.latency import LOST_MS, LossyDelay
    d = LossyDelay(EmpiricalDelay.from_trace([0.3, 0.4, 0.5, 0.9]),
                   loss_prob=0.5)
    s = streaming.fast_path_stream(KEY, TABLE, d, n=11, trials=20_000,
                                   chunk=8_192, shard=False)
    # heavy loss must produce undecided trials (lost hops reach LOST_MS)
    assert int(np.asarray(s.n_undecided)[0]) > 0
    assert float(np.asarray(s.max_ms)[0]) < LOST_MS


# ---------------------------------------------------------------------------
# MarkovRegimes: validation + the chain itself
# ---------------------------------------------------------------------------

def test_transition_row_sum_validation():
    bad = dataclasses.replace(
        gray_failure(11),
        transition=jnp.array([[0.9, 0.2, 0.0],
                              [0.1, 0.9, 0.0],
                              [0.2, 0.0, 0.8]], jnp.float32))
    with pytest.raises(ValueError, match="sum to 1"):
        bad.validate()
    with pytest.raises(ValueError, match=">= 0"):
        dataclasses.replace(
            gray_failure(11),
            transition=jnp.array([[1.5, -0.5, 0.0],
                                  [0.1, 0.9, 0.0],
                                  [0.2, 0.0, 0.8]],
                                 jnp.float32)).validate()
    with pytest.raises(ValueError, match="unique"):
        dataclasses.replace(gray_failure(11),
                            names=("a", "a", "b")).validate()
    with pytest.raises(ValueError, match="start"):
        dataclasses.replace(gray_failure(11), start=3).validate()


def test_chain_prefix_property():
    """z[e] does not depend on the scan length — the property that makes
    regime occupancy chunk-invariant (longer runs only append epochs)."""
    reg = gray_failure(11, p_fail=0.2, p_recover=0.3)
    k = jax.random.PRNGKey(9)
    short = np.asarray(reg.sequence(k, 10))
    long = np.asarray(reg.sequence(k, 50))
    np.testing.assert_array_equal(short, long[:10])
    assert short[0] == reg.start


def test_identity_transition_pins_chain():
    reg = dataclasses.replace(gray_failure(11), start=1,
                              transition=jnp.eye(3, dtype=jnp.float32))
    zs = np.asarray(reg.sequence(KEY, 20))
    np.testing.assert_array_equal(zs, np.ones(20, np.int32))


def test_regimes_config_roundtrip():
    reg = gray_failure(11, epoch_trials=1024)
    cfg = json.loads(json.dumps(reg.to_config()))
    reg2 = MarkovRegimes.from_config(cfg, 11)
    assert reg2.to_config() == reg.to_config()
    assert reg2.names == reg.names
    np.testing.assert_allclose(np.asarray(reg2.transition),
                               np.asarray(reg.transition))
    with pytest.raises(ValueError, match="cluster size"):
        MarkovRegimes.from_config(cfg)          # crashed list needs n


# ---------------------------------------------------------------------------
# Regime-modulated streaming: degeneracy, invariance, compile discipline
# ---------------------------------------------------------------------------

def test_single_regime_bit_identical_to_iid():
    """The acceptance contract: a 1-regime chain inheriting the base delay
    is bit-identical to the plain i.i.d. stream on decide bits, counts and
    histogram (the mixed-delay wrapper passes the key through unfolded)."""
    only = MarkovRegimes(names=("only",), delays=(None,),
                         transition=jnp.ones((1, 1), jnp.float32))
    plain = _race(KEY, trials=50_000, chunk=8_192)
    mod = _race(KEY, trials=50_000, chunk=8_192, regimes=only)
    assert isinstance(mod, RegimeStreamSummary)
    assert int(np.asarray(mod.occupancy).sum()) == 50_000
    for attr in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, attr)),
                                      np.asarray(getattr(mod, attr)),
                                      err_msg=attr)


def test_single_regime_bit_identical_on_full_sort_path():
    """Same degeneracy under k_max=None (the full-sort reference path):
    the regime layer must not disturb either lowering."""
    only = MarkovRegimes(names=("only",), delays=(None,),
                         transition=jnp.ones((1, 1), jnp.float32))
    plain = _race(KEY, trials=30_000, chunk=8_192, k_max=None)
    mod = _race(KEY, trials=30_000, chunk=8_192, k_max=None, regimes=only)
    for attr in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, attr)),
                                      np.asarray(getattr(mod, attr)),
                                      err_msg=attr)


def test_regime_occupancy_chunk_invariant():
    """Trial t's regime is z[t // epoch_trials] in TRIAL index space, so
    the occupancy split cannot depend on how trials are chunked."""
    reg = gray_failure(11, epoch_trials=1_000, p_fail=0.1, p_recover=0.3)
    occ = {}
    for chunk in (4_096, 8_192, 16_384):
        s = _race(jax.random.PRNGKey(5), trials=37_111, chunk=chunk,
                  regimes=reg)
        occ[chunk] = np.asarray(s.occupancy)
        assert int(occ[chunk].sum()) == 37_111
    np.testing.assert_array_equal(occ[4_096], occ[8_192])
    np.testing.assert_array_equal(occ[4_096], occ[16_384])


def test_regime_slices_merge_to_marginal_totals():
    reg = gray_failure(11, epoch_trials=2_048, p_fail=0.1, p_recover=0.3)
    s = _race(jax.random.PRNGKey(3), trials=60_000, chunk=8_192,
              regimes=reg)
    tot = s.total()
    per = [s.regime(i) for i in range(s.n_regimes)]
    assert int(np.asarray(tot.n_trials)[0]) == 60_000
    for attr in ("n_trials", "n_fast", "n_recovery", "n_undecided"):
        merged = sum(int(np.asarray(getattr(p, attr))[0]) for p in per)
        assert merged == int(np.asarray(getattr(tot, attr))[0]), attr
    np.testing.assert_array_equal(
        sum(np.asarray(p.hist) for p in per), np.asarray(tot.hist))
    # occupancy counts every trial exactly once
    np.testing.assert_array_equal(
        np.asarray(s.occupancy),
        np.asarray([int(np.asarray(p.n_trials)[0]) for p in per]))


def test_regime_merge_and_report():
    reg = gray_failure(11, epoch_trials=1_024, p_fail=0.1, p_recover=0.3)
    a = _race(jax.random.PRNGKey(1), trials=20_000, chunk=8_192,
              regimes=reg)
    b = _race(jax.random.PRNGKey(2), trials=20_000, chunk=8_192,
              regimes=reg)
    m = a.merge(b)
    assert int(np.asarray(m.occupancy).sum()) == 40_000
    rep = m.report()
    assert rep["names"] == ["baseline", "degraded", "partitioned"]
    assert abs(sum(rep["occupancy_frac"]) - 1.0) < 1e-9
    other = dataclasses.replace(a, names=("x", "y", "z"))
    with pytest.raises(ValueError, match="regime sets"):
        a.merge(other)


def test_regime_stream_single_compile_per_geometry():
    """One fresh trace for a new (table shape, chunk count, R, epoch)
    geometry; transition weights, environment parameters and the seed are
    traced operands — re-weighting the chain adds ZERO compiles."""
    kw = dict(trials=40_000, chunk=8_192)
    t0 = engine.TRACE_COUNTS["race_stream_regimes"]
    _race(jax.random.PRNGKey(1), regimes=gray_failure(11, epoch_trials=2_048),
          **kw)
    assert engine.TRACE_COUNTS["race_stream_regimes"] - t0 == 1
    _race(jax.random.PRNGKey(2),
          regimes=gray_failure(11, epoch_trials=2_048, p_fail=0.2,
                               p_recover=0.5, loss_prob=0.1,
                               degraded_scale_ms=2.0),
          **kw)
    assert engine.TRACE_COUNTS["race_stream_regimes"] - t0 == 1


def test_regime_fixed_seed_anchor():
    """Fixed-seed regression anchor (tests/regen_anchors.py): integer-
    exact occupancy + decide counts pin the regime key domain and the
    epoch->trial mapping; p50 pins the sketch within float tolerance."""
    s = streaming.race_stream(
        jax.random.PRNGKey(42), TABLE, OFFS, None, n=11, k_proposers=2,
        trials=100_000, chunk=16_384, shard=False,
        regimes=gray_failure(11, epoch_trials=2_048))
    assert np.asarray(s.occupancy).tolist() == REGIME_ANCHOR["occupancy"]
    assert int(np.asarray(s.n_fast)[0]) == REGIME_ANCHOR["n_fast"]
    assert int(np.asarray(s.n_recovery)[0]) == REGIME_ANCHOR["n_recovery"]
    assert (int(np.asarray(s.n_undecided)[0])
            == REGIME_ANCHOR["n_undecided"])
    assert (abs(float(np.asarray(s.quantile(0.5))[0])
                - REGIME_ANCHOR["p50_ms"])
            < 1e-4 * REGIME_ANCHOR["p50_ms"])


@pytest.mark.slow
def test_ten_million_trial_three_regime_single_compile():
    """The ISSUE acceptance row: a 10^7-trial 3-regime race sweep in ONE
    compile per table shape, fixed-size per-regime state."""
    t0 = engine.TRACE_COUNTS["race_stream_regimes"]
    s = _race(jax.random.PRNGKey(7), trials=10_000_000, chunk=262_144,
              regimes=gray_failure(11, epoch_trials=8_192))
    assert engine.TRACE_COUNTS["race_stream_regimes"] - t0 == 1
    occ = np.asarray(s.occupancy, np.int64)
    assert int(occ.sum()) == 10_000_000
    assert occ[0] > 0                     # the chain spends time in baseline
    assert int(np.asarray(s.n_trials)[0]) == 10_000_000
    assert s.by_regime.hist.shape == (3, 1,
                                      streaming.sketch_bins(s.precision))


# ---------------------------------------------------------------------------
# Workload / Experiment declarative configs
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(pick=st.integers(0, 4), k=st.integers(2, 4),
       delta=st.floats(0.0, 1.0), p=st.floats(0.001, 0.2))
def test_workload_to_dict_roundtrips_every_constructor(pick, k, delta, p):
    """Satellite acceptance: Workload.from_dict(w.to_dict()) is a fixpoint
    for every constructor (compare dicts — delay fields hold arrays)."""
    from repro.api.experiment import Workload
    wl = [Workload.conflict_free(),
          Workload.race(k=k, delta_ms=delta),
          Workload.mixed(conflict_frac=p, k=k, delta_ms=delta),
          Workload.wan(k=k, inter_region_ms=10.0 + 100.0 * p),
          Workload.lossy(loss_prob=p, k=k, delta_ms=delta)][pick]
    d = wl.to_dict()
    d2 = Workload.from_dict(json.loads(json.dumps(d))).to_dict()
    assert d == d2


def test_workload_roundtrip_with_trace_and_regimes():
    from repro.api.experiment import Workload
    wl = Workload.race(
        k=2, delta_ms=0.2,
        delay=EmpiricalDelay.from_trace([0.3, 0.5, 0.4, 0.9],
                                        n_quantiles=16),
        regimes=gray_failure(7, epoch_trials=512))
    d = wl.to_dict()
    wl2 = Workload.from_dict(json.loads(json.dumps(d)))
    assert wl2.to_dict() == d
    # lazy configs resolve once the cluster size is known
    assert isinstance(wl2.delay_for(7), EmpiricalDelay)
    r = wl2.regimes_for(7)
    assert isinstance(r, MarkovRegimes) and r.names == (
        "baseline", "degraded", "partitioned")


def test_workload_kind_shorthand():
    from repro.api.experiment import Workload
    wl = Workload.from_dict({"kind": "race", "k": 3, "delta_ms": 0.1})
    assert wl.k_proposers == 3 and wl.delta_ms == 0.1
    with pytest.raises(ValueError, match="unknown workload kind"):
        Workload.from_dict({"kind": "nope"})
    with pytest.raises(ValueError, match="at least 2"):
        Workload.from_dict({"kind": "race", "k": 1})


def test_workload_from_dict_rejects_unknown_keys():
    """Satellite: a typo'd key raises a ValueError naming the offending
    key AND the valid set — on both the kind-shorthand and plain paths —
    instead of an opaque ctor TypeError or a silently dropped knob."""
    from repro.api.experiment import Workload
    with pytest.raises(ValueError) as ei:
        Workload.from_dict({"kind": "race", "k": 2, "delta_mss": 0.1})
    assert "delta_mss" in str(ei.value) and "delta_ms" in str(ei.value)
    with pytest.raises(ValueError) as ei:
        Workload.from_dict({"k_proposers": 2, "trialz": 7})
    assert "trialz" in str(ei.value) and "valid keys" in str(ei.value)
    # recovery is a real key on both paths
    wl = Workload.from_dict({"kind": "race", "k": 2,
                             "recovery": "uncoordinated"})
    assert wl.recovery == "uncoordinated"
    with pytest.raises(ValueError, match="unknown recovery rule"):
        Workload.from_dict({"kind": "race", "k": 2, "recovery": "oracle"})


def test_workload_from_dict_rejects_unknown_delay_kind():
    """A delay config whose registry name does not resolve fails up front
    with the known-kind list, including when nested under a wrapper."""
    from repro.api.experiment import Workload
    from repro.montecarlo.latency import delay_kinds
    with pytest.raises(ValueError) as ei:
        Workload.from_dict({"kind": "race", "k": 2,
                            "delay": {"kind": "warp"}})
    msg = str(ei.value)
    assert "warp" in msg
    for known in delay_kinds():
        assert known in msg
    with pytest.raises(ValueError, match="warp"):
        Workload.from_dict({
            "k_proposers": 2,
            "delay": {"kind": "lossy", "loss_prob": 0.1,
                      "inner": {"kind": "warp"}}})


def test_workload_recovery_roundtrip():
    """recovery serializes (dropped at default), round-trips, and reaches
    the scenario spec."""
    from repro.api.experiment import Workload
    wl = Workload.race(k=2, delta_ms=0.2, recovery="uncoordinated")
    d = wl.to_dict()
    assert d["recovery"] == "uncoordinated"
    assert "recovery" not in Workload.race(k=2, delta_ms=0.2).to_dict()
    wl2 = Workload.from_dict(json.loads(json.dumps(d)))
    assert wl2.recovery == "uncoordinated"
    assert wl2.scenario(5).spec.recovery == "uncoordinated"


@pytest.mark.parametrize("name", ["diurnal_wan.json", "trace_replay.json"])
def test_experiment_from_committed_config(name):
    """The committed example scenario configs load, lower and stream; the
    regime decomposition covers every trial exactly once."""
    from repro.api.experiment import Experiment
    exp = Experiment.from_config(os.path.join(EXAMPLES, name))
    exp = dataclasses.replace(exp, trials=20_000, chunk=8_192, shard=False)
    r = exp.run("montecarlo")
    assert isinstance(r.stream, RegimeStreamSummary)
    assert int(np.asarray(r.stream.occupancy).sum()) == 20_000
    assert int(np.asarray(r.stream.n_trials).sum()) \
        == 20_000 * len(exp.systems)
    for v in r.summary.values():
        assert np.asarray(v).shape == (len(exp.systems),)


def test_experiment_from_config_dict_and_system_kinds():
    from repro.api.experiment import Experiment, system_from_config
    from repro.core.quorum import (ExplicitQuorumSystem,
                                  WeightedQuorumSystem)
    s = system_from_config({"kind": "cardinality", "preset":
                            "paper_headline", "n": 11})
    assert isinstance(s, QuorumSpec) and (s.q1, s.q2c, s.q2f) == (9, 3, 7)
    g = system_from_config({"kind": "grid", "cols": 3, "rows": 3, "n": 11})
    assert isinstance(g, ExplicitQuorumSystem) and g.n == 11
    w = system_from_config({"kind": "weighted",
                            "weights": [2, 2, 1, 1, 1], "t1": 6, "t2c": 2,
                            "t2f": 5})
    assert isinstance(w, WeightedQuorumSystem)
    with pytest.raises(ValueError, match="unknown system kind"):
        system_from_config({"kind": "pyramid"})

    exp = Experiment.from_config({
        "systems": [{"kind": "cardinality", "n": 5, "q1": 4, "q2c": 2,
                     "q2f": 4}],
        "workload": {"kind": "race", "k": 2, "delta_ms": 0.2},
        "samples": 2_000, "seed": 3})
    r = exp.run("montecarlo")
    assert r.backend == "montecarlo" and len(r.labels) == 1


def test_planner_accepts_serialized_workload_dict():
    """Satellite: the planner's wire format takes full Workload.to_dict()
    payloads (not just the ctor shorthand), and regime chains change the
    search geometry key."""
    from repro.api.experiment import Workload
    from repro.planner import Planner
    from repro.planner.service import PlanQuery, resolve_workload

    full = Workload.lossy(loss_prob=0.05, k=3, delta_ms=0.4).to_dict()
    wl = resolve_workload(json.loads(json.dumps(full)))
    assert wl.to_dict() == full
    assert resolve_workload({"kind": "wan",
                             "inter_region_ms": 25.0}).inter_region_ms \
        == 25.0

    p = Planner()
    base = dict(n=7, family="cardinality", trials=10_000, chunk=8_192,
                shard=False)
    with_reg = Workload.race(
        k=2, delta_ms=0.2,
        regimes=gray_failure(7, epoch_trials=512).to_config()).to_dict()
    q1 = PlanQuery(workload=json.loads(json.dumps(with_reg)), **base)
    q2 = PlanQuery(workload={"kind": "race", "k": 2, "delta_ms": 0.2},
                   **base)
    assert p.geometry_key(q1) != p.geometry_key(q2)


# ---------------------------------------------------------------------------
# RunSpec: one spec object carries the engine knobs; legacy kwargs are gone
# ---------------------------------------------------------------------------

def test_runspec_is_the_only_knob_path():
    """The PR-9 keyword shims are deleted: run/summary/stream take exactly
    (key, table), and any legacy engine-knob keyword is a plain
    TypeError, not a DeprecationWarning."""
    scen = k_way_race(2, 0.25)
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # no warning of any kind
        new = scen.with_spec(trials=20_000, chunk=8_192,
                             shard=False).stream(KEY, TABLE)
    assert int(np.asarray(new.n_trials)[0]) == 20_000
    with pytest.raises(TypeError):
        scen.stream(KEY, TABLE, trials=20_000)
    with pytest.raises(TypeError):
        scen.stream(KEY, TABLE, k_max=None)
    with pytest.raises(TypeError):
        scen.run(KEY, TABLE, samples=4_000)
    with pytest.raises(TypeError):
        scen.summary(KEY, TABLE, trials=20_000)


def test_runspec_merged_and_sentinel_k_max():
    spec = RunSpec().merged(trials=5, chunk=1_024)
    assert spec.trials == 5 and spec.chunk == 1_024
    assert spec.merged().trials == 5          # no-op merge keeps values
    # explicit k_max=None (full-sort reference) survives the spec plumbing
    scen = k_way_race(2, 0.25).with_spec(trials=12_000, chunk=8_192,
                                      shard=False)
    full = scen.with_spec(k_max=None).stream(KEY, TABLE)
    auto = scen.stream(KEY, TABLE)
    np.testing.assert_array_equal(np.asarray(full.hist),
                                  np.asarray(auto.hist))


def test_runspec_carries_recovery_rule():
    """``recovery`` rides the spec like every other knob: the entry rate is
    rule-invariant, the streamed histograms differ, and an unknown rule
    raises before any engine work."""
    scen = k_way_race(2, 0.25).with_spec(trials=20_000, chunk=8_192,
                                         shard=False)
    sc = scen.stream(KEY, TABLE)
    su = scen.with_spec(recovery="uncoordinated").stream(KEY, TABLE)
    np.testing.assert_array_equal(np.asarray(sc.n_recovery),
                                  np.asarray(su.n_recovery))
    assert not np.array_equal(np.asarray(sc.hist), np.asarray(su.hist))
    with pytest.raises(ValueError, match="unknown recovery rule"):
        scen.with_spec(recovery="oracle").stream(KEY, TABLE)


def test_scenario_spec_carries_regimes_through_workload():
    from repro.api.experiment import Workload
    wl = Workload.race(k=2, delta_ms=0.25,
                       regimes=gray_failure(11, epoch_trials=1_024))
    scen = wl.scenario(11)
    assert scen.spec.regimes is not None
    s = scen.with_spec(trials=20_000, chunk=8_192, shard=False).stream(
        KEY, TABLE)
    assert isinstance(s, RegimeStreamSummary)
    assert int(np.asarray(s.occupancy).sum()) == 20_000
