"""The declarative Experiment layer (``repro.api``): one object, three
backends.  Cross-backend consistency is the point — a quorum system
declared once must model-check clean, agree between the Monte-Carlo engine
and the discrete-event simulator, and expose one normalized Results shape.
"""
import jax
import pytest

from repro.api import BACKENDS, Experiment, Results, Workload, sweep
from repro.core.quorum import (ExplicitQuorumSystem, QuorumSpec,
                               WeightedQuorumSystem)
from repro.montecarlo import engine
from repro.montecarlo.streaming import StreamSettings

# Small enough for the modelcheck backend, rich enough to span all three
# system families.
SYSTEMS = [QuorumSpec(5, 4, 2, 4),
           ExplicitQuorumSystem.grid(1).embed(5),            # n=3 grid in 5
           WeightedQuorumSystem((2, 1, 1, 1, 1), 5, 2, 4)]


@pytest.fixture(scope="module")
def race_exp():
    return Experiment(systems=SYSTEMS,
                      workload=Workload.race(k=2, delta_ms=0.3),
                      samples=20_000)


# ---------------------------------------------------------------------------
# one Experiment object, unmodified, against all three backends
# ---------------------------------------------------------------------------

def test_one_experiment_runs_on_all_three_backends(race_exp):
    res = sweep(race_exp, BACKENDS)
    assert set(res) == {"montecarlo", "des", "modelcheck"}
    for backend, r in res.items():
        assert isinstance(r, Results) and r.backend == backend
        assert r.labels == race_exp.labels
    # montecarlo and des agree on the workload's physics (§4 contract):
    # fast-path p50 within 5% relative, P(recovery) within 0.05 absolute
    mc, des = res["montecarlo"], res["des"]
    for i in range(len(SYSTEMS)):
        p50_mc = float(mc.summary["p50_ms"][i])
        p50_des = float(des.summary["p50_ms"][i])
        assert abs(p50_mc - p50_des) / p50_des < 0.05, (i, p50_mc, p50_des)
        rec_mc = float(mc.summary["recovery_rate"][i])
        rec_des = float(des.summary["recovery_rate"][i])
        assert abs(rec_mc - rec_des) < 0.05, (i, rec_mc, rec_des)
    # the model checker signs off on every declared system
    assert all(v["ok"] for v in res["modelcheck"].safety)
    # fault tolerance is backend-independent (computed from the masks)
    assert mc.fault_tolerance == des.fault_tolerance
    assert mc.fault_tolerance[0]["phase2_fast"] == 1        # n=5, q2f=4


def test_modelcheck_backend_flags_invalid_system():
    """Teeth: an Eq.14-violating system must come back unsafe, with the
    violating trace attached."""
    bad = ExplicitQuorumSystem.from_spec(QuorumSpec(3, 2, 2, 2))
    r = Experiment(systems=[bad], max_states=500_000).run("modelcheck")
    assert r.safety[0]["ok"] is False
    assert r.safety[0]["violation"] == "Consistency"
    assert r.safety[0]["trace"]
    assert r.summary["safe"][0] == 0.0


def test_modelcheck_backend_rejects_large_n():
    exp = Experiment(systems=[QuorumSpec.paper_headline(11)])
    with pytest.raises(ValueError, match="n<=5"):
        exp.run("modelcheck")


def test_montecarlo_single_compile_and_masked_lowering(race_exp):
    """The declarative layer must not cost extra compiles: re-running the
    same experiment reuses the engine's jit cache, and its lowering is the
    mask table (general, since the batch mixes families)."""
    table = race_exp.lower()
    assert "q" not in table                       # mixed families
    assert table["p1_w"].shape == (3, table["p1_w"].shape[1], 5)
    race_exp.run("montecarlo")
    before = dict(engine.TRACE_COUNTS)
    race_exp.run("montecarlo")
    assert engine.TRACE_COUNTS == before


def test_cardinality_experiment_lowers_to_q_specialization():
    exp = Experiment(systems=[QuorumSpec(5, 4, 2, 4), QuorumSpec(5, 5, 1, 4)],
                     workload=Workload.race(k=2, delta_ms=0.3),
                     samples=2_000)
    assert "q" in exp.lower()
    out = exp.run("montecarlo")
    assert out.raw["latency_ms"].shape == (2, 2_000)


# ---------------------------------------------------------------------------
# Results shape
# ---------------------------------------------------------------------------

def test_results_to_dict_and_system_view(race_exp):
    r = race_exp.run("montecarlo")
    d = r.to_dict()
    lab = r.labels[0]
    assert f"{lab}.p50_ms" in d and f"{lab}.ft_fast" in d
    assert d[f"{lab}.p50_ms"] == pytest.approx(float(r.summary["p50_ms"][0]))
    view = r.system(lab)
    assert view["p50_ms"] == d[f"{lab}.p50_ms"]
    assert view["ft_phase2_fast"] == r.fault_tolerance[0]["phase2_fast"]


def test_results_is_a_pytree(race_exp):
    r = race_exp.run("montecarlo")
    leaves, treedef = jax.tree_util.tree_flatten(r)
    assert leaves
    r2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(r2, Results)
    assert r2.labels == r.labels and r2.backend == r.backend
    doubled = jax.tree_util.tree_map(lambda x: x * 2, r)
    assert float(doubled.summary["p50_ms"][0]) == pytest.approx(
        2 * float(r.summary["p50_ms"][0]))


def test_duplicate_labels_are_disambiguated():
    exp = Experiment(systems=[QuorumSpec(5, 4, 2, 4), QuorumSpec(5, 4, 2, 4)])
    assert len(set(exp.labels)) == 2


# ---------------------------------------------------------------------------
# faults and guardrails
# ---------------------------------------------------------------------------

def test_faults_cross_backend_agreement():
    """Crashing past the phase-1 budget (q1=4 of n=5, two crashes) must kill
    liveness identically on both executable backends."""
    exp = Experiment(systems=[QuorumSpec(5, 4, 2, 4)],
                     workload=Workload.race(k=2, delta_ms=0.3),
                     faults=(0, 1), samples=4_000)
    mc = exp.run("montecarlo")
    des = exp.run("des")
    assert float(mc.summary["undecided_rate"][0]) == 1.0
    assert des.summary["undecided_rate"][0] == 1.0


def test_mixed_cluster_sizes_rejected():
    with pytest.raises(ValueError, match="system 1"):
        Experiment(systems=[QuorumSpec(5, 4, 2, 4),
                            ExplicitQuorumSystem.grid(1)]).lower()


def test_raw_masks_rejected_on_set_level_backends():
    masks_only = ExplicitQuorumSystem.grid(1).to_masks().embed(5)
    exp = Experiment(systems=[QuorumSpec(5, 4, 2, 4), masks_only],
                     samples=500)
    exp.run("montecarlo")                         # engine path is fine
    with pytest.raises(ValueError, match="montecarlo"):
        exp.run("des")


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        Experiment(systems=[QuorumSpec(5, 4, 2, 4)], backend="paxi")
    with pytest.raises(ValueError, match="backend"):
        Experiment(systems=[QuorumSpec(5, 4, 2, 4)]).run("paxi")


def test_wan_workload_refuses_des_backend():
    exp = Experiment(systems=[QuorumSpec(5, 4, 2, 4)],
                     workload=Workload.wan(k=2), samples=500)
    exp.run("montecarlo")
    with pytest.raises(ValueError, match="montecarlo backend"):
        exp.run("des")


# ---------------------------------------------------------------------------
# streaming (trials=): fixed-memory trial scaling through the same front door
# ---------------------------------------------------------------------------

def test_streamed_experiment_matches_materialized_summary():
    """``trials=`` must expose the same normalized keys with values that
    agree with the materializing path at the same sample count (within the
    sketch's relative error + Monte-Carlo noise across PRNG layouts)."""
    kw = dict(systems=SYSTEMS, workload=Workload.race(k=2, delta_ms=0.3),
              compute_fault_tolerance=False)
    mat = Experiment(samples=40_000, **kw).run("montecarlo")
    stream = Experiment(trials=40_000, stream=StreamSettings(chunk=8_192),
                        **kw).run("montecarlo")
    assert stream.raw is None and stream.stream is not None
    assert set(mat.summary) <= set(stream.summary)
    assert "p999_ms" in stream.summary
    for i in range(len(SYSTEMS)):
        p50_m = float(mat.summary["p50_ms"][i])
        p50_s = float(stream.summary["p50_ms"][i])
        assert abs(p50_s - p50_m) / p50_m < 0.05, (i, p50_m, p50_s)
        rec_m = float(mat.summary["recovery_rate"][i])
        rec_s = float(stream.summary["recovery_rate"][i])
        assert abs(rec_s - rec_m) < 0.02, (i, rec_m, rec_s)


def test_streamed_experiment_is_a_pytree_with_stream_state():
    r = Experiment(systems=[QuorumSpec(5, 4, 2, 4)], trials=3_000,
                   stream=StreamSettings(chunk=1_024),
                   compute_fault_tolerance=False).run("montecarlo")
    assert int(r.stream.n_trials[0]) == 3_000
    leaves, treedef = jax.tree_util.tree_flatten(r)
    r2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(r2, Results) and r2.stream is not None
    assert int(r2.stream.n_trials[0]) == 3_000


def test_streamed_experiment_rejects_bad_trials():
    with pytest.raises(ValueError, match="trials"):
        Experiment(systems=[QuorumSpec(5, 4, 2, 4)], trials=0)
