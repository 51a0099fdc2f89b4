"""Unit tests for the batched Monte-Carlo scenario engine
(``repro.montecarlo``): the unified mask-table lowering, delay models,
scenarios, summaries, and batched-vs-solo agreement."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.quorum import QuorumSpec, all_valid_specs
from repro.montecarlo import (CrashedDelay, LossyDelay, ParetoDelay,
                              Scenario, ShiftedLognormalDelay, WanDelay,
                              build_mask_table, engine, scenarios)

KEY = jax.random.PRNGKey(7)
FFP = QuorumSpec.paper_headline(11)
FP = QuorumSpec.fast_paxos(11)


# ---------------------------------------------------------------------------
# tables + traced batching
# ---------------------------------------------------------------------------

def test_mask_table_mixed_n_rejected():
    with pytest.raises(ValueError, match="mixes cluster sizes"):
        build_mask_table([FFP, QuorumSpec(7, 6, 2, 6)])


def test_mask_table_specializes_cardinality_batches():
    t = build_mask_table([FFP, FP])
    assert t["q"].shape == (2, 3) and t["q"].dtype == jnp.int32
    assert bool((t["q"][0] == jnp.array([9, 3, 7])).all())
    assert "q" not in build_mask_table([FFP, FP], specialize=False)


def test_raw_spec_tables_rejected():
    """The pre-mask-table (M, 3) signature was removed after its
    deprecation release: entry points demand a build_mask_table dict."""
    raw = jnp.array([[9, 3, 7]], jnp.int32)
    with pytest.raises(TypeError, match="build_mask_table"):
        engine.race(KEY, raw, jnp.array([0.0, 0.3]), n=11, k_proposers=2,
                    samples=64)
    with pytest.raises(TypeError, match="build_mask_table"):
        engine.fast_path(KEY, raw, n=11, samples=64)
    assert not hasattr(engine, "race_masked")        # aliases gone too
    assert not hasattr(engine, "fast_path_masked")
    with pytest.raises(ImportError):
        import repro.core.jax_sim  # noqa: F401 — shim deleted


def test_batched_fast_path_matches_solo_tables():
    """Common random numbers: every spec of a batch sees the same sampled
    delays, so scoring a spec alone must reproduce its batch row exactly."""
    specs = [FP, FFP, QuorumSpec(11, 11, 1, 6)]
    table = build_mask_table(specs)
    batched = engine.fast_path(KEY, table, n=11, samples=40_000)
    for i, s in enumerate(specs):
        solo = engine.fast_path(KEY, build_mask_table([s]), n=11,
                                samples=40_000)[0]
        assert float(jnp.abs(batched[i] - solo).max()) < 1e-6


def test_batched_race_matches_solo_tables():
    specs = [FP, FFP]
    table = build_mask_table(specs)
    out = engine.race(KEY, table, jnp.array([0.0, 0.3]), n=11,
                      k_proposers=2, samples=30_000)
    for i, s in enumerate(specs):
        solo = engine.race(KEY, build_mask_table([s]), jnp.array([0.0, 0.3]),
                           n=11, k_proposers=2, samples=30_000)
        assert bool((out["recovery"][i] == solo["recovery"][0]).all())
        assert float(jnp.abs(out["latency_ms"][i]
                             - solo["latency_ms"][0]).max()) < 1e-6


def test_fast_path_monotone_in_quorum_size():
    table = build_mask_table([QuorumSpec(11, 11, 1, 7),
                              QuorumSpec(11, 11, 1, 9)])
    lat = engine.fast_path(KEY, table, n=11, samples=50_000)
    assert float(lat[0].mean()) < float(lat[1].mean())


def test_classic_path_slower_than_fast():
    table = build_mask_table([FFP])
    fast = engine.fast_path(KEY, table, n=11, samples=30_000)
    classic = engine.classic_path(KEY, table, n=11, samples=30_000)
    # classic adds the client->leader relay hop
    assert float(classic.mean()) > float(fast.mean())


def test_recovery_probability_decreasing_in_interval():
    """Fig. 2c: larger inter-command intervals -> fewer recoveries."""
    table = build_mask_table([FFP])
    ps = []
    for d in (0.0, 0.3, 0.8, 2.0):
        out = engine.race(KEY, table, jnp.array([0.0, d]), n=11,
                          k_proposers=2, samples=30_000)
        ps.append(float(out["recovery"].mean()))
    assert ps[0] >= ps[1] >= ps[2] >= ps[3]
    assert ps[3] < 0.01


def test_full_valid_space_single_trace():
    """The whole Eq.13/14-valid space for n=7 (hundreds of specs) must cost
    one race trace, and a different same-shape table must cost zero."""
    specs = list(all_valid_specs(7))
    assert len(specs) > 50
    table = build_mask_table(specs)
    before = engine.TRACE_COUNTS["race"]
    out = engine.race(KEY, table, jnp.array([0.0, 0.2]), n=7,
                      k_proposers=2, samples=2_000)
    assert out["latency_ms"].shape == (len(specs), 2_000)
    assert engine.TRACE_COUNTS["race"] - before == 1
    table2 = build_mask_table(list(reversed(specs)))
    engine.race(KEY, table2, jnp.array([0.0, 0.7]), n=7,
                k_proposers=2, samples=2_000)
    assert engine.TRACE_COUNTS["race"] - before == 1


def test_race_outcomes_partition_k3():
    table = build_mask_table([FFP])
    out = engine.race(KEY, table, jnp.array([0.0, 0.2, 0.4]), n=11,
                      k_proposers=3, samples=10_000)
    total = (out["reached_fast"].astype(jnp.int32)
             + out["recovery"].astype(jnp.int32)
             + out["undecided"].astype(jnp.int32))
    assert bool((total == 1).all())
    assert bool((out["fast_winner"][out["reached_fast"]] >= 0).all())
    assert bool((out["fast_winner"][~out["reached_fast"]] == -1).all())


def test_race_outcomes_partition_under_loss():
    """With lossy hops the three outcomes must still partition: a quorum of
    acceptor votes whose 2bs never reach the learner is NOT a fast commit —
    it falls back to recovery (or undecided), never both flags at once."""
    from repro.montecarlo.latency import default_delay
    table = build_mask_table([FFP])
    out = engine.race(KEY, table, jnp.array([0.0, 0.3]),
                      LossyDelay(default_delay(), 0.4),
                      n=11, k_proposers=2, samples=20_000)
    total = (out["reached_fast"].astype(jnp.int32)
             + out["recovery"].astype(jnp.int32)
             + out["undecided"].astype(jnp.int32))
    assert bool((total == 1).all())
    decided = ~out["undecided"]
    assert bool((out["latency_ms"][decided] < engine.UNDECIDED_MS).all())
    assert bool(out["undecided"].any())          # 40% loss must bite
    assert bool((out["fast_winner"][~out["reached_fast"]] == -1).all())


def test_kernel_and_ref_paths_identical():
    table = build_mask_table([FFP, FP])
    kw = dict(n=11, k_proposers=2, samples=8_000)
    offs = jnp.array([0.0, 0.3])
    o_ref = engine.race(KEY, table, offs, use_kernel=False, **kw)
    o_ker = engine.race(KEY, table, offs, use_kernel=True, **kw)
    assert bool((o_ref["fast_winner"] == o_ker["fast_winner"]).all())
    assert float(jnp.abs(o_ref["latency_ms"]
                         - o_ker["latency_ms"]).max()) < 1e-6


# ---------------------------------------------------------------------------
# delay models
# ---------------------------------------------------------------------------

def test_delay_models_are_pytrees():
    for model in (ShiftedLognormalDelay(), ParetoDelay(),
                  LossyDelay(ShiftedLognormalDelay(), 0.05),
                  WanDelay.symmetric(30.0, n=11, k_proposers=2)):
        leaves = jax.tree_util.tree_leaves(model)
        assert leaves, model
        rebuilt = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(model), leaves)
        assert type(rebuilt) is type(model)


def test_pareto_tail_heavier_than_lognormal():
    ln = ShiftedLognormalDelay().sample_hops(KEY, (200_000,))
    pa = ParetoDelay().sample_hops(KEY, (200_000,))
    tail = lambda x: float(jnp.quantile(x, 0.999) / jnp.quantile(x, 0.5))
    assert tail(pa) > tail(ln)


def test_wan_delay_topology():
    wan = WanDelay.symmetric(30.0, n=6, k_proposers=2, n_regions=3)
    d = wan.sample_hops(KEY, (1000, 6), kind="to_learner")
    # acceptors 0 and 3 share the learner's region (round-robin): no 30 ms hop
    assert float(d[:, 0].mean()) < 5.0 < float(d[:, 1].mean())
    prop = wan.sample_hops(KEY, (1000, 6, 2), kind="proposal")
    assert prop.shape == (1000, 6, 2)
    # proposer 1 (region 1) is local to acceptors 1 and 4 only
    assert float(prop[:, 1, 1].mean()) < 5.0 < float(prop[:, 0, 1].mean())


def test_lossy_delay_marks_losses():
    d = LossyDelay(ShiftedLognormalDelay(), 0.2).sample_hops(KEY, (50_000,))
    frac = float((d >= 1e8).mean())
    assert 0.17 < frac < 0.23


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def test_conflict_free_scenario_equals_fast_path():
    table = build_mask_table([FFP])
    scen = scenarios.conflict_free(n=11)
    out = scen.with_spec(samples=5_000).run(KEY, table)
    direct = engine.fast_path(KEY, table, n=11, samples=5_000)
    assert float(jnp.abs(out["latency_ms"] - direct).max()) < 1e-6
    assert not bool(out["recovery"].any())


def test_mixed_workload_blend():
    table = build_mask_table([FFP])
    s = scenarios.mixed_workload(0.01, 0.3, n=11).with_spec(
        samples=20_000).summary(KEY, table)
    assert float(s["p99_ms"][0]) >= float(s["p50_ms"][0]) > 0
    assert 0.0 <= float(s["recovery_rate"][0]) <= 0.01


def test_wan_scenario_latency_dominated_by_geography():
    table = build_mask_table([FFP])
    local = scenarios.conflict_free(n=11).with_spec(
        samples=5_000).summary(KEY, table)
    geo = scenarios.wan(n=11, inter_region_ms=30.0)
    geo = Scenario(geo.name, geo.n, 1, geo.offsets_ms[:1], geo.delay)
    far = geo.with_spec(samples=5_000).summary(KEY, table)
    assert float(far["p50_ms"][0]) > 10 * float(local["p50_ms"][0])


def test_lossy_scenario_increases_recovery():
    table = build_mask_table([FFP])
    clean = scenarios.k_way_race(2, 0.3, n=11).with_spec(
        samples=30_000).run(KEY, table)
    lossy = scenarios.lossy_acceptors(0.15, delta_ms=0.3, n=11).with_spec(
        samples=30_000).run(KEY, table)
    p_clean = float(clean["recovery"].mean() + clean["undecided"].mean())
    p_lossy = float(lossy["recovery"].mean() + lossy["undecided"].mean())
    assert p_lossy > p_clean + 0.05
    # with 15% loss per hop some instances can still decide via recovery
    assert bool(lossy["reached_fast"].any())


# ---------------------------------------------------------------------------
# summaries (engine.summarize is the one summary path for all layers)
# ---------------------------------------------------------------------------

def test_summarize_shapes():
    lat = jax.random.uniform(KEY, (3, 1000)) + 1.0
    s = engine.summarize(lat)
    for v in s.values():
        assert v.shape == (3,)


def test_summarize_percentiles_monotone():
    out = engine.race(KEY, build_mask_table([FFP, FP]),
                      jnp.array([0.0, 0.3]), n=11, k_proposers=2,
                      samples=20_000)
    s = engine.summarize(out)
    for i in range(2):
        p50, p95 = float(s["p50_ms"][i]), float(s["p95_ms"][i])
        p99, mx = float(s["p99_ms"][i]), float(s["max_ms"][i])
        assert 0 < p50 <= p95 <= p99 <= mx, (i, p50, p95, p99, mx)


def test_summarize_excludes_undecided_from_latency_stats():
    """Undecided instances (LOST_MS sentinel latencies) must not drag the
    sentinel into the quantiles — they are reported as a rate instead."""
    lat = jnp.array([[1.0, 2.0, 3.0, engine.BIG]])
    out = {"latency_ms": lat,
           "undecided": lat >= engine.UNDECIDED_MS,
           "reached_fast": jnp.array([[True, True, False, False]]),
           "recovery": jnp.array([[False, False, True, False]])}
    s = engine.summarize(out)
    assert float(s["max_ms"][0]) == 3.0
    assert float(s["p99_ms"][0]) < 3.01
    assert float(s["mean_ms"][0]) == pytest.approx(2.0)
    assert float(s["undecided_rate"][0]) == pytest.approx(0.25)
    assert float(s["fast_rate"][0]) == pytest.approx(0.5)
    assert float(s["recovery_rate"][0]) == pytest.approx(0.25)


def test_summarize_fixed_seed_regression_anchor():
    """Fixed-seed anchor: engine refactors that silently change the sampled
    race structure (key splits, draw order, presort layout) move these
    numbers far outside tolerance; refactors that only re-lower the decide
    step keep them bit-stable.  Regenerate with
    tests/regen_anchors.py::montecarlo if sampling changes *on purpose*."""
    out = engine.race(jax.random.PRNGKey(123), build_mask_table([FFP]),
                      jnp.array([0.0, 0.25]), n=11, k_proposers=2,
                      samples=20_000)
    s = engine.summarize(out)
    assert float(s["p50_ms"][0]) == pytest.approx(1.22053, rel=1e-3)
    assert float(s["recovery_rate"][0]) == pytest.approx(0.01725, rel=1e-3)
    assert float(out["latency_ms"][0, 0]) == pytest.approx(1.232813,
                                                           rel=1e-5)
    assert float(out["latency_ms"][0, 1]) == pytest.approx(1.6301,
                                                           rel=1e-5)


# ---------------------------------------------------------------------------
# general quorum systems through the scenario layer
# ---------------------------------------------------------------------------

def test_crashed_delay_loses_every_hop_of_crashed_acceptors():
    crashed = jnp.zeros((6,), bool).at[2].set(True)
    d = CrashedDelay(ShiftedLognormalDelay(), crashed)
    hops = d.sample_hops(KEY, (500, 6), kind="to_learner")
    assert bool((hops[:, 2] >= 1e8).all())
    assert bool((hops[:, 0] < 1e8).all())
    prop = d.sample_hops(KEY, (500, 6, 2), kind="proposal")
    assert bool((prop[:, 2, :] >= 1e8).all())
    leaves = jax.tree_util.tree_leaves(d)
    assert leaves                      # registered pytree (traced crash set)


def test_scenario_with_faults_matches_manual_crash_wrap():
    scen = scenarios.k_way_race(2, 0.3, n=11)
    wrapped = scen.with_faults((0, 5))
    assert isinstance(wrapped.delay, CrashedDelay)
    assert bool(wrapped.delay.crashed[0]) and bool(wrapped.delay.crashed[5])
    assert scen.with_faults(()) is scen


def test_grid_wan_scenario_masked_outcomes_partition():
    scen, masks = scenarios.grid_wan(cols=3, k=2, delta_ms=0.3)
    out = scen.with_spec(samples=4_000).run(
        KEY, build_mask_table([masks]))
    total = (out["reached_fast"].astype(jnp.int32)
             + out["recovery"].astype(jnp.int32)
             + out["undecided"].astype(jnp.int32))
    assert bool((total == 1).all())
    assert out["latency_ms"].shape == (1, 4_000)
    # two full rows = two full regions: a fast commit pays the WAN hop
    lat = jnp.where(out["undecided"], jnp.nan, out["latency_ms"])
    assert float(jnp.nanmedian(lat)) > 30.0


def test_weighted_scenario_beats_uniform_on_fast_path():
    """Concentrating weight shrinks the fast-path *cardinality*: with three
    weight-2 acceptors a fast quorum needs fewer machines than the uniform
    q2f = ceil(3n/4), so its order statistic (p50) can only be lower or
    equal; sanity-check the masked scenario wiring end-to-end."""
    scen, masks = scenarios.weighted_acceptors(delta_ms=0.3)
    table = build_mask_table([masks, QuorumSpec.fast_paxos(11)])
    s = scen.with_spec(samples=8_000).summary(KEY, table)
    assert float(s["p50_ms"][0]) <= float(s["p50_ms"][1]) + 1e-6
    assert float(s["undecided_rate"][0]) == 0.0


def test_weighted_heavy_crash_hurts_more_than_light():
    heavy, masks = scenarios.weighted_acceptors(crashed=(0, 1))   # two 2s
    light, _ = scenarios.weighted_acceptors(crashed=(9, 10))      # two 1s
    table = build_mask_table([masks])
    s_heavy = heavy.with_spec(samples=6_000).summary(KEY, table)
    s_light = light.with_spec(samples=6_000).summary(KEY, table)
    assert float(s_heavy["p50_ms"][0]) >= float(s_light["p50_ms"][0]) - 1e-6
