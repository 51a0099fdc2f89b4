"""Frontier subsystem tests (``repro.frontier``): property suite for the
dominance kernel, family-generator validity (intersection requirements +
model checking at small n), streamed-vs-materializing cross-validation,
the legacy per-spec reference containment, and the fixed-seed n=11
frontier anchor that makes silent frontier drift fail loudly."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from benchmarks.quorum_sweep import enumerate_valid, minimal_frontier
from repro.core.model_check import explore
from repro.core.quorum import (QuorumSpec, RelaxedQuorumSpec, ffp_card_ok,
                               ffp_min_q2c, relaxed_card_ok)
from repro.frontier import (Axis, FrontierResult, cardinality_family,
                            default_axes, dominates, grid_family,
                            maximal_mask, pareto_mask, quantize,
                            relaxed_family, score_systems, weighted_family)
from repro.montecarlo import build_mask_table, engine, streaming
from repro.montecarlo.streaming import StreamSummary

MIXED_AXES = (Axis("lat"), Axis("ft", maximize=True), Axis("rate"))


def _rand_values(seed: int, m: int, a: int = 3) -> np.ndarray:
    """Small integer grid so ties and duplicate vectors actually occur."""
    rng = np.random.RandomState(seed)
    return rng.randint(0, 5, size=(m, a)).astype(np.float64)


# ---------------------------------------------------------------------------
# dominance kernel properties
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.integers(1, 40))
def test_frontier_maximal_and_covering(seed, m):
    """No frontier point is dominated, and every excluded point is
    dominated by some *frontier* point (quantized dominance is a strict
    partial order, so chains terminate at maximal elements)."""
    v = _rand_values(seed, m)
    q = quantize(v, MIXED_AXES)
    mask = maximal_mask(q)
    assert mask.any()
    for i in range(m):
        if mask[i]:
            assert not any(dominates(q, j, i) for j in range(m))
        else:
            assert any(mask[j] and dominates(q, j, i) for j in range(m))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.integers(2, 40))
def test_frontier_invariant_under_permutation(seed, m):
    v = _rand_values(seed, m)
    mask = pareto_mask(v, MIXED_AXES)
    perm = np.random.RandomState(seed + 1).permutation(m)
    np.testing.assert_array_equal(pareto_mask(v[perm], MIXED_AXES),
                                  mask[perm])


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.integers(2, 30))
def test_frontier_invariant_under_duplicate_rows(seed, m):
    """Appending copies of existing rows changes no membership: ties never
    dominate each other, so a duplicate lands on the same side as its
    original."""
    v = _rand_values(seed, m)
    mask = pareto_mask(v, MIXED_AXES)
    dup = np.random.RandomState(seed + 2).randint(0, m, size=5)
    mask2 = pareto_mask(np.vstack([v, v[dup]]), MIXED_AXES)
    np.testing.assert_array_equal(mask2[:m], mask)
    np.testing.assert_array_equal(mask2[m:], mask[dup])


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.integers(2, 30))
def test_equal_quantized_vectors_share_membership(seed, m):
    """Epsilon quantization collapses ties: rows indistinguishable at the
    measurement's precision (equal quantized vectors) are kept or excluded
    together."""
    axes = (Axis("lat", eps=0.05, relative=True),
            Axis("ft", maximize=True),
            Axis("rate", eps=0.1))
    rng = np.random.RandomState(seed)
    v = np.stack([np.exp(rng.uniform(-1, 1, m)),
                  rng.randint(0, 3, m).astype(float),
                  rng.uniform(0, 1, m)], axis=1)
    q = quantize(v, axes)
    mask = pareto_mask(v, axes)
    for i in range(m):
        for j in range(m):
            if (q[i] == q[j]).all():
                assert mask[i] == mask[j]


def test_epsilon_collapses_within_sketch_error_ties():
    """A point worse by far less than the sketch's relative error must tie
    with (not be dominated by) the exact point once eps matches the sketch
    precision — and still be dominated with eps=0."""
    exact_axes = (Axis("lat"), Axis("ft", maximize=True))
    eps_axes = (Axis("lat", eps=0.01, relative=True),
                Axis("ft", maximize=True))
    v = np.array([[1.0, 3.0],
                  [1.002, 3.0]])     # 0.2% slower: inside 1% sketch error
    np.testing.assert_array_equal(pareto_mask(v, exact_axes),
                                  [True, False])
    np.testing.assert_array_equal(pareto_mask(v, eps_axes), [True, True])
    # well outside the sketch error the domination comes back
    v[1, 0] = 1.1
    np.testing.assert_array_equal(pareto_mask(v, eps_axes), [True, False])


def test_absolute_epsilon_on_rate_axis():
    axes = (Axis("rate", eps=0.01), Axis("ft", maximize=True))
    v = np.array([[0.500, 2.0], [0.502, 2.0], [0.520, 2.0]])
    mask = pareto_mask(v, axes)
    assert mask[0] and mask[1] and not mask[2]


def test_nan_scores_are_worst_on_any_orientation():
    """NaN (nothing decided) loses on minimize AND maximize axes, and an
    all-NaN batch still returns a frontier (all tied-worst)."""
    axes = (Axis("lat"), Axis("ft", maximize=True))
    v = np.array([[1.0, 2.0], [np.nan, 3.0], [1.0, np.nan]])
    q = quantize(v, axes)
    assert q[1, 0] == -np.inf and q[2, 1] == -np.inf
    mask = pareto_mask(v, axes)
    assert mask[0] and mask[1] and not mask[2]
    assert pareto_mask(np.full((3, 2), np.nan), axes).all()


def test_quantize_validates_shapes_and_axes():
    with pytest.raises(ValueError, match="axes"):
        quantize(np.zeros((3, 2)), MIXED_AXES)
    with pytest.raises(ValueError, match="eps"):
        Axis("bad", eps=-1.0)
    with pytest.raises(ValueError, match="relative"):
        Axis("bad", relative=True)


# ---------------------------------------------------------------------------
# family generators: validity + model checking at small n
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [4, 5, 7, 11])
def test_cardinality_family_is_the_full_valid_space(n):
    mem = cardinality_family(n)
    triples = {(m.system.q1, m.system.q2c, m.system.q2f) for m in mem}
    brute = {(q1, q2c, q2f)
             for q1 in range(1, n + 1) for q2c in range(1, n + 1)
             for q2f in range(1, n + 1) if ffp_card_ok(n, q1, q2c, q2f)}
    assert triples == brute
    assert len(mem) == len(triples)                  # no duplicates
    assert all(m.system.is_valid() for m in mem)
    labels = [m.label for m in mem]
    assert len(set(labels)) == len(labels)


def test_sweep_enumeration_matches_family():
    legacy = {(s.q1, s.q2c, s.q2f) for s in enumerate_valid(11)}
    fam = {(m.system.q1, m.system.q2c, m.system.q2f)
           for m in cardinality_family(11)}
    assert legacy == fam


def test_grid_family_valid_and_embedding_invariant_ft():
    mem = grid_family(12)
    assert [m.label for m in mem] == ["grid.3x1", "grid.3x2", "grid.3x3",
                                      "grid.3x4"]
    for m in mem:
        assert m.system.is_valid()                   # Eqs. 11/12 exactly
        ft = m.masks(12).fault_tolerance()
        # two crashes in distinct rows break every row-pair fast quorum
        assert ft["phase2_fast"] == 1
        # zero-weight embed acceptors never help a crash set kill a
        # quorum: budgets are embedding-invariant
        assert ft == m.masks(14).fault_tolerance()


def test_weighted_family_valid_weight_inequalities():
    for n in (5, 11):
        mem = weighted_family(n)
        assert mem
        for m in mem:
            w = m.system
            W = w.total
            assert w.t1 + w.t2c > W                  # Eq. 13, weight space
            assert w.t1 + 2 * w.t2f > 2 * W          # Eq. 14, weight space
            assert m.masks(n).n == n


@pytest.mark.parametrize("n,count", [(4, 7), (5, 13), (11, 125)])
def test_relaxed_family_is_the_relaxed_only_space(n, count):
    """``relaxed_family`` enumerates exactly the triples that satisfy the
    Relaxed Paxos predicate (Eq.14 alone) but NOT the FFP pair — the
    systems the joint frontier can only reach by relaxing intersection."""
    mem = relaxed_family(n)
    assert len(mem) == count
    triples = {(m.system.q1, m.system.q2c, m.system.q2f) for m in mem}
    brute = {(q1, q2c, q2f)
             for q1 in range(1, n + 1) for q2c in range(1, n + 1)
             for q2f in range(1, n + 1)
             if relaxed_card_ok(n, q1, q2c, q2f)
             and not ffp_card_ok(n, q1, q2c, q2f)}
    assert triples == brute
    labels = [m.label for m in mem]
    assert len(set(labels)) == len(labels)
    for m in mem:
        assert isinstance(m.system, RelaxedQuorumSpec)
        assert m.system.is_valid()
        # the honest recovery-phase-1 budget: rounds above a classic round
        # need q1_full = max(q1, n + 1 - q2c)
        ft = m.system.fault_tolerance()
        assert ft["phase1"] == n - m.system.q1_full


def test_relaxed_system_survives_joint_frontier():
    """At least one relaxed-valid / FFP-invalid system is Pareto-optimal
    on the joint n=11 frontier — the paper-level payoff of relaxing
    intersection (the full assertion set runs in benchmarks.quorum_sweep
    .run_relaxed)."""
    members = cardinality_family(11) + relaxed_family(11)
    r = score_systems(members, trials=24_576, chunk=8_192, shard=False,
                      seed=ANCHOR_SEED)
    relaxed_on = [l for l in r.frontier_labels if l.startswith("relaxed[")]
    assert relaxed_on, "no relaxed member survived the joint reduction"
    # relaxed[5,2,9] strictly beats every FFP triple at q1=5 on ft_classic
    # (FFP forces q2c >= 7 at q1=5) while matching its latency axes
    assert "relaxed[5,2,9]" in r.labels
    row = r.row("relaxed[5,2,9]")
    assert row["ft_classic"] == 9.0 and row["ft_phase1"] == 1.0


def test_relaxed_spec_to_explicit_refuses():
    """Lowering a relaxed spec to an explicit set system would silently
    flatten the per-round phase-1 semantics — it must refuse."""
    with pytest.raises(TypeError, match="per-round"):
        RelaxedQuorumSpec(5, 1, 1, 5).to_explicit()


def test_small_grid_and_weighted_members_model_check_clean():
    """Every n<=5 grid/weighted member explores clean: the set-level
    safety backstop behind the frontier's Monte-Carlo scores."""
    small = [m for m in grid_family(5) + weighted_family(5, (1, 2))
             + weighted_family(4, (1,)) if m.system.n <= 5]
    assert small                                     # grid.3x1 at least
    for m in small:
        r = explore(m.system, max_states=150_000)
        assert r.ok and r.violation is None, (m.label, r.violation)


# ---------------------------------------------------------------------------
# cross-validation: streamed scorer vs the materializing path
# ---------------------------------------------------------------------------

def test_score_small_trials_bit_identical_to_materializing():
    """Satellite contract: for T <= chunk (single device) the scorer's
    streams ARE the materializing engine plus a reduction — sketch state
    bit-for-bit, quantile axes bit-for-bit."""
    specs = [QuorumSpec.paper_headline(11), QuorumSpec.fast_paxos(11)]
    trials, seed = 3_000, 7
    r = score_systems(specs, trials=trials, chunk=8_192, shard=False,
                      seed=seed)

    key = jax.random.PRNGKey(seed)
    k_fast, k_race = jax.random.split(key)
    table = build_mask_table([s.to_masks() for s in specs])
    ref_fast = StreamSummary.from_outcomes(
        streaming._lat_only_outcomes(
            engine.fast_path(k_fast, table, n=11, samples=trials),
            fast=True))
    offs = 0.2 * jnp.arange(2, dtype=jnp.float32)
    ref_race = StreamSummary.from_outcomes(
        engine.race(k_race, table, offs, n=11, k_proposers=2,
                    samples=trials))
    for ref, got in ((ref_fast, r.streams["fast"]),
                     (ref_race, r.streams["race"])):
        for f in ("n_trials", "n_fast", "n_recovery", "n_undecided",
                  "hist"):
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(ref, f)), f)
    vals = np.asarray(r.values)
    np.testing.assert_array_equal(vals[:, 0],
                                  np.asarray(ref_fast.quantile(0.5),
                                             np.float64))
    np.testing.assert_array_equal(vals[:, 1],
                                  np.asarray(ref_race.quantile(0.999),
                                             np.float64))


# ---------------------------------------------------------------------------
# the n=11 cardinality frontier: legacy containment + fixed-seed anchor
# ---------------------------------------------------------------------------

# Anchor parameters — mirrored in tests/regen_anchors.py::frontier.
ANCHOR_TRIALS = 49_152
ANCHOR_CHUNK = 16_384
ANCHOR_SEED = 0

# Regenerate with ``PYTHONPATH=src python tests/regen_anchors.py`` when the
# engine's sampling or the axis set changes on purpose.
ANCHOR_MEMBERS = [
    "card[1,11,11]", "card[10,2,7]", "card[11,1,6]", "card[2,10,11]",
    "card[3,9,10]", "card[4,8,10]", "card[5,7,11]", "card[5,7,9]",
    "card[6,6,11]", "card[6,6,9]", "card[7,5,8]", "card[8,4,8]",
    "card[9,3,7]",
]
ANCHOR_ROW = {                       # card[9,3,7], the paper's headline
    "fast_p50_ms": 1.2031513452529907,
    "race_p999_ms": 2.677736282348633,
    "p_recovery": 0.048055013020833336,
    "ft_fast": 4.0, "ft_phase1": 2.0, "ft_classic": 8.0,
}


@pytest.fixture(scope="module")
def scored_n11():
    return score_systems(cardinality_family(11), trials=ANCHOR_TRIALS,
                         chunk=ANCHOR_CHUNK, shard=False, seed=ANCHOR_SEED)


def test_frontier_contains_legacy_minimal_reference(scored_n11):
    """Satellite: the scored n=11 frontier contains every member of the
    legacy quorum-size-minimal reference (quorum_sweep.minimal_frontier),
    and every scored member carries the minimal valid q2c for its q1 (a
    smaller-q2c sibling dominates via ft_classic under common random
    numbers)."""
    members = set(scored_n11.frontier_labels)
    minimal = {s.label for s in minimal_frontier(enumerate_valid(11))}
    assert minimal <= members, sorted(minimal - members)
    fam = cardinality_family(11)
    for i in scored_n11.frontier_indices:
        spec = fam[i].system
        assert spec.q2c == ffp_min_q2c(11, spec.q1), spec


def test_fixed_seed_frontier_anchor(scored_n11):
    """Fixed-seed anchor: frontier membership + the paper-headline row.
    Anything that moves these without an intentional sampling/axis change
    is silently reshaping the benchmark — exactly what this test exists
    to catch.  Regenerate via tests/regen_anchors.py::frontier."""
    assert sorted(scored_n11.frontier_labels) == ANCHOR_MEMBERS
    row = scored_n11.row("card[9,3,7]")
    assert row["on_frontier"]
    for k, v in ANCHOR_ROW.items():
        assert row[k] == pytest.approx(v, rel=1e-6), (k, row[k], v)


def test_frontier_single_compile_per_stream_path(scored_n11):
    """Scoring a second same-shape batch re-enters the same compiles."""
    before = dict(engine.TRACE_COUNTS)
    score_systems(cardinality_family(11), trials=ANCHOR_TRIALS,
                  chunk=ANCHOR_CHUNK, shard=False, seed=ANCHOR_SEED + 1)
    assert engine.TRACE_COUNTS == before


# ---------------------------------------------------------------------------
# FrontierResult + front doors
# ---------------------------------------------------------------------------

def test_frontier_result_pytree_table_and_to_dict(scored_n11):
    leaves, treedef = jax.tree_util.tree_flatten(scored_n11)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.labels == scored_n11.labels
    assert rebuilt.axes == scored_n11.axes
    np.testing.assert_array_equal(np.asarray(rebuilt.mask),
                                  np.asarray(scored_n11.mask))

    d = scored_n11.to_dict()
    assert d["n_systems"] == len(scored_n11.labels)
    assert d["n_frontier"] == len(scored_n11.frontier_indices)
    assert d["card[9,3,7].on_frontier"] == 1.0
    assert "card[9,3,7].race_p999_ms" in d

    tab = scored_n11.table()
    assert "card[9,3,7]" in tab and "race_p999_ms" in tab
    assert len(scored_n11.table(frontier_only=False).splitlines()) \
        == len(scored_n11.labels) + 2


def test_experiment_frontier_front_door():
    """``Experiment.frontier()`` / ``api.frontier`` run the scorer with
    the experiment's systems and config."""
    from repro.api import Experiment, Workload, frontier
    systems = [QuorumSpec.paper_headline(11), QuorumSpec.fast_paxos(11)]
    stream = streaming.StreamSettings(chunk=8_192, shard=False)
    exp = Experiment(systems=systems,
                     workload=Workload.race(k=2, delta_ms=0.2),
                     trials=20_000, stream=stream,
                     compute_fault_tolerance=False)
    fr = exp.frontier()
    assert fr.labels == ("card[9,3,7]", "card[6,6,9]")
    # the two landmarks trade fault tolerance for latency: both survive
    assert fr.frontier_labels == fr.labels
    fr2 = frontier(systems, trials=20_000, stream=stream)
    np.testing.assert_array_equal(np.asarray(fr2.values),
                                  np.asarray(fr.values))


def test_experiment_frontier_honors_faults():
    """A faulted experiment scores the frontier with the crashes applied:
    killing more acceptors than the fast path tolerates leaves nothing
    decided on the fast stream (NaN latency axis, which orients to
    worst)."""
    from repro.api import Experiment, Workload
    spec = QuorumSpec.paper_headline(11)          # q2f=7: tolerates 4
    base = Experiment(systems=[spec], workload=Workload.race(k=2),
                      trials=4_000,
                      stream=streaming.StreamSettings(chunk=8_192,
                                                      shard=False),
                      compute_fault_tolerance=False)
    import dataclasses
    faulty = dataclasses.replace(base, faults=(0, 1, 2, 3, 4))
    fr_ok, fr_bad = base.frontier(), faulty.frontier()
    assert int(np.asarray(fr_bad.streams["fast"].n_undecided)[0]) == 4_000
    assert np.isnan(np.asarray(fr_bad.values)[0, 0])
    assert not np.isnan(np.asarray(fr_ok.values)[0, 0])


def test_default_axes_match_axis_names():
    from repro.frontier.score import AXIS_NAMES
    axes = default_axes()
    assert tuple(a.name for a in axes) == AXIS_NAMES
    assert axes[0].relative and axes[0].eps == streaming.DEFAULT_PRECISION


# ---------------------------------------------------------------------------
# sharded scoring (real under the CI 8-device job)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (run under "
                           "--xla_force_host_platform_device_count)")
def test_sharded_score_counts_exact_and_members_sane():
    specs = [QuorumSpec.paper_headline(11), QuorumSpec.fast_paxos(11),
             QuorumSpec.majority_fast(11)]
    trials = 30_011                      # deliberately not divisible
    r = score_systems(specs, trials=trials, chunk=2_048, shard=True)
    for s in r.streams.values():
        assert [int(x) for x in np.asarray(s.n_trials)] == [trials] * 3
    # neither landmark dominates the other whatever the device count
    assert {"card[9,3,7]", "card[6,6,9]"} <= set(r.frontier_labels)
