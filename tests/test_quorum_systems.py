"""Differential safety for general quorum systems (grids, weighted,
explicit): every system the masked engine accepts must (a) pass the
set-level Eq.11/12 checkers, (b) model-check clean, and (c) produce engine
decide-bits that match brute-force set semantics.  Per Relaxed Paxos
(Howard & Mortier 2022), exhaustive checking of small systems against the
simulator and model checker is what licenses the fast path.
"""
import glob
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.model_check import explore
from repro.core.quorum import (PAD_THRESHOLD, ExplicitQuorumSystem,
                               QuorumMasks, QuorumSpec,
                               WeightedQuorumSystem, all_valid_specs)
from repro.frontier.families import (cardinality_family, grid_family,
                                     weighted_family)
from repro.kernels.quorum_tally import ref as qt_ref
from repro.montecarlo import build_mask_table, engine

KEY = jax.random.PRNGKey(11)


def _small_systems():
    """Every n <= 5 explicit/grid system exercised by the suite: the §6 grid
    construction, explicit enumerations of FFP-valid cardinality specs, and
    weighted systems converted to their minimal-quorum explicit form."""
    out = [("grid_1col", ExplicitQuorumSystem.grid(1))]           # n = 3
    for spec in [QuorumSpec(3, 2, 2, 3), QuorumSpec(4, 4, 1, 3),
                 QuorumSpec(4, 3, 2, 4), QuorumSpec(5, 4, 2, 4)]:
        out.append((f"card_{spec.n}_{spec.q1}{spec.q2c}{spec.q2f}",
                    ExplicitQuorumSystem.from_spec(spec.validate())))
    out.append(("weighted_n3",
                WeightedQuorumSystem((1, 1, 2), 3, 2, 3).validate()
                .to_explicit()))
    out.append(("weighted_n5",
                WeightedQuorumSystem((2, 1, 1, 1, 1), 5, 2, 4).validate()
                .to_explicit()))
    return out


SMALL_SYSTEMS = _small_systems()
IDS = [name for name, _ in SMALL_SYSTEMS]
SYSTEMS = [sys for _, sys in SMALL_SYSTEMS]


# ---------------------------------------------------------------------------
# (a) the engine accepts exactly the systems the set checkers accept
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("system", SYSTEMS, ids=IDS)
def test_engine_accepted_systems_are_set_valid(system):
    assert system.is_valid()                      # Eq.11 + Eq.12, exact sets
    table = build_mask_table([system])            # the engine's acceptance
    assert table["p1_w"].shape[-1] == system.n


# ---------------------------------------------------------------------------
# (b) model checker: no reachable safety violation for any accepted system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("system", SYSTEMS, ids=IDS)
def test_accepted_systems_model_check_clean(system):
    cap = 120_000 if system.n <= 4 else 60_000
    r = explore(system, max_states=cap)
    assert r.ok, (r.violation, r.trace)
    assert r.states > 1_000                       # non-trivial exploration


def test_invalid_explicit_system_violates_consistency():
    """Teeth check: the explicit-system path must reproduce the cardinality
    counterexample — (3, 2, 2, 2) breaks Eq.14 and two values get decided."""
    bad = ExplicitQuorumSystem.from_spec(QuorumSpec(3, 2, 2, 2))
    assert not bad.is_valid()
    r = explore(bad, max_states=500_000)
    assert not r.ok and r.violation == "Consistency"
    assert r.trace and r.trace[0] == "Init"


# ---------------------------------------------------------------------------
# (c) engine decide-bits == brute-force set semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("system", SYSTEMS, ids=IDS)
def test_mask_satisfaction_matches_set_semantics(system):
    """masks.satisfied / the masked-tally oracle / _sat_time must all agree
    with 'the subset contains some enumerated quorum', for every subset."""
    masks = system.to_masks()
    quorums = {"p1": system.p1, "p2c": system.p2c, "p2f": system.p2f}
    for r in range(system.n + 1):
        for members in itertools.combinations(range(system.n), r):
            s = set(members)
            for phase in ("p1", "p2c", "p2f"):
                expect = any(q <= s for q in quorums[phase])
                assert masks.satisfied(s, phase) == expect, (s, phase)
            # engine decide bit: all members vote value 0, rest abstain
            votes = np.full((1, system.n), -1, np.int32)
            votes[0, list(s)] = 0
            got = qt_ref.masked_tally(jnp.asarray(votes),
                                      jnp.asarray(masks.p2f_w),
                                      jnp.asarray(masks.p2f_t), 1)
            assert bool((got[0] >= 0).any()) == \
                any(q <= s for q in system.p2f), s
            # arrival saturation: members arrive at 1ms, rest never
            arr = jnp.where(jnp.asarray(votes[0]) == 0, 1.0, engine.BIG)
            perm = jnp.argsort(arr).astype(jnp.int32)[None]
            tt = engine._sat_time(jnp.sort(arr)[None], perm,
                                  jnp.asarray(masks.p1_w),
                                  jnp.asarray(masks.p1_t))
            assert bool(tt[0] < engine.UNDECIDED_MS) == \
                any(q <= s for q in system.p1), s


# ---------------------------------------------------------------------------
# property tests: cardinality round-trips through to_masks()
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(q1=st.integers(1, 5), q2c=st.integers(1, 5), q2f=st.integers(1, 5),
       seed=st.integers(0, 10_000))
def test_masked_decide_equals_threshold_decide(q1, q2c, q2f, seed):
    """For any valid n=5 cardinality spec, the general masked lowering must
    be bit-identical to the k-th-order-statistic specialization ("q" table)
    on the same sampled race (shapes are fixed, so the whole property run
    costs one compile per lowering)."""
    spec = QuorumSpec(5, q1, q2c, q2f)
    if not spec.is_valid():
        return
    key = jax.random.PRNGKey(seed)
    offs = jnp.array([0.0, 0.25])
    kw = dict(n=5, k_proposers=2, samples=512)
    thr = engine.race(key, build_mask_table([spec]), offs, **kw)
    msk = engine.race(key, build_mask_table([spec], specialize=False),
                      offs, **kw)
    for k in thr:
        assert bool((thr[k] == msk[k]).all()), (k, spec)


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 24), q=st.integers(1, 24), seed=st.integers(0, 9999))
def test_sat_time_on_ones_row_is_kth_order_statistic(n, q, seed):
    """An all-ones mask row with threshold q <= n saturates exactly at the
    q-th order statistic (the threshold path's gather)."""
    q = min(q, n)
    x = jnp.sort(jax.random.uniform(jax.random.PRNGKey(seed), (7, n)),
                 axis=-1)
    perm = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (7, n))
    got = engine._sat_time(x, perm, jnp.ones((1, n)),
                           jnp.array([float(q)]))
    want = engine._kth(x, jnp.int32(q))
    assert bool((got == want).all()), (n, q)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(3, 6), q1=st.integers(1, 6), q2c=st.integers(1, 6),
       q2f=st.integers(1, 6))
def test_arithmetic_validity_equals_set_validity(n, q1, q2c, q2f):
    """Eq.13/14 arithmetic == Eq.11/12 on the enumerated explicit system."""
    q1, q2c, q2f = min(q1, n), min(q2c, n), min(q2f, n)
    spec = QuorumSpec(n, q1, q2c, q2f)
    assert spec.is_valid() == ExplicitQuorumSystem.from_spec(spec).is_valid()


# ---------------------------------------------------------------------------
# mask-table plumbing
# ---------------------------------------------------------------------------

def test_mask_table_padding_and_embedding():
    grid = ExplicitQuorumSystem.grid(3).to_masks().embed(11)   # 9 -> 11
    card = QuorumSpec.paper_headline(11)
    table = build_mask_table([card, grid])
    g1 = max(1, len(ExplicitQuorumSystem.grid(3).p1))
    assert table["p1_w"].shape == (2, g1, 11)
    # padded rows are never satisfiable: zero weight, huge threshold
    assert float(table["p1_w"][0, 1:].sum()) == 0.0
    assert bool((table["p1_t"][0, 1:] > 1e6).all())
    # embedded acceptors 9, 10 carry no weight in any grid quorum
    assert float(table["p1_w"][1, :, 9:].sum()) == 0.0


def test_mask_table_rejects_mixed_n_and_garbage():
    with pytest.raises(ValueError, match="system 1"):
        build_mask_table([QuorumSpec.paper_headline(11), QuorumSpec(7, 6, 2, 6)])
    with pytest.raises(ValueError):
        engine.race(KEY, {"p1_w": jnp.ones((1, 1, 5))},
                    jnp.array([0.0, 0.1]), n=5, k_proposers=2,
                    samples=8)


def test_mask_table_mixed_n_error_names_offender():
    """Satellite: the n-mismatch error must say *which* system is wrong,
    not surface as an opaque XLA broadcast error."""
    grid = ExplicitQuorumSystem.grid(3)          # n = 9
    with pytest.raises(ValueError) as exc:
        build_mask_table([QuorumSpec.paper_headline(11), grid])
    msg = str(exc.value)
    assert "system 1" in msg and "n=9" in msg and "n=11" in msg
    assert "embed" in msg                        # actionable hint


def _stacked_reference(systems, specialize=True):
    """The table as plain per-array stacks of each system's masks, each
    phase padded to the batch's row count with zero-weight rows that no
    arrival set can meet."""
    masks = [s if isinstance(s, QuorumMasks) else s.to_masks()
             for s in systems]
    ref = {}
    for p, ph in enumerate(("p1", "p2c", "p2f")):
        g = max(m.groups[p] for m in masks)
        ws, ts = [], []
        for m in masks:
            w, t = getattr(m, ph + "_w"), getattr(m, ph + "_t")
            pad = g - w.shape[0]
            ws.append(np.concatenate([w, np.zeros((pad, m.n), np.float32)]))
            ts.append(np.concatenate([t, np.full(pad, PAD_THRESHOLD,
                                                 np.float32)]))
        ref[ph + "_w"] = np.stack([np.asarray(w, np.float32) for w in ws])
        ref[ph + "_t"] = np.stack([np.asarray(t, np.float32) for t in ts])
    qs = [m.cardinality_q() for m in masks]
    if specialize and all(q is not None for q in qs):
        ref["q"] = np.array(qs, np.int32)
    return ref


FFP_SPACE = [m.masks(11) for m in cardinality_family(11)]
TABLE_CASES = {
    # (systems, specialize, carries "q")
    "ffp_space_n11": (FFP_SPACE, True, True),
    "grid_and_weighted": ([QuorumSpec.paper_headline(11),
                           grid_family(11)[-1].masks(11),
                           weighted_family(11)[0].masks(11)], True, False),
    "embedded_n3": ([QuorumSpec.paper_headline(11),
                     ExplicitQuorumSystem.grid(1).to_masks().embed(11)],
                    True, False),
    "unspecialized": (FFP_SPACE, False, False),
}


@pytest.mark.parametrize("case", list(TABLE_CASES))
def test_mask_table_equals_the_stacked_reference(case):
    """The host-assembled table is the per-array stack, key for key and
    bit for bit, as uncommitted strong-typed arrays on the default
    device (so ``shard_map`` may still place it on a mesh)."""
    systems, specialize, has_q = TABLE_CASES[case]
    table = build_mask_table(systems, specialize=specialize)
    ref = _stacked_reference(systems, specialize)
    assert list(table) == list(ref)
    assert ("q" in table) == has_q
    assert table["p1_w"].shape[0] == len(systems)
    for k, want in ref.items():
        got = table[k]
        assert isinstance(got, jax.Array), k
        assert (got.shape, got.dtype) == (want.shape, want.dtype), k
        assert not got.weak_type and not got.committed, k
        assert got.devices() == {jax.devices()[0]}, k
        assert np.asarray(got).tobytes() == want.tobytes(), k


def test_mask_table_is_one_transfer_and_no_device_program(monkeypatch,
                                                          tmp_path):
    """The table is filled on the host and crosses in one ``device_put``:
    no ``jnp`` stacking, and no executable runs while it is built."""
    from jax.profiler import ProfileData
    puts = []
    real_put = jax.device_put

    def counting_put(*args, **kwargs):
        puts.append(args)
        return real_put(*args, **kwargs)

    def refuse(*args, **kwargs):
        raise AssertionError("mask table built with jnp on the device")

    build_mask_table(FFP_SPACE)                   # imports, first use
    monkeypatch.setattr(engine.jax, "device_put", counting_put)
    for name in ("stack", "asarray", "array", "concatenate"):
        monkeypatch.setattr(engine.jnp, name, refuse)
    jax.profiler.start_trace(str(tmp_path))
    try:
        table = jax.block_until_ready(build_mask_table(FFP_SPACE))
    finally:
        jax.profiler.stop_trace()
    assert len(puts) == 1 and "q" in table
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events]
    assert "DevicePutWithSharding" in names       # the trace saw the transfer
    assert not [n for n in names if "Execute" in n]   # no program ran


def test_fast_and_classic_path_lowerings_bit_identical():
    specs = [QuorumSpec.paper_headline(11), QuorumSpec.fast_paxos(11)]
    spec_t = build_mask_table(specs)                       # "q" gathers
    gen_t = build_mask_table(specs, specialize=False)      # masked saturation
    assert bool((engine.fast_path(KEY, spec_t, n=11, samples=8_000)
                 == engine.fast_path(KEY, gen_t, n=11, samples=8_000)).all())
    assert bool((engine.classic_path(KEY, spec_t, n=11, samples=8_000)
                 == engine.classic_path(KEY, gen_t, n=11,
                                        samples=8_000)).all())


def test_all_valid_n4_specs_roundtrip_masked():
    """Whole n=4 valid space: general lowering == "q" specialization, one
    compile per lowering, one table."""
    specs = list(all_valid_specs(4))
    assert specs
    offs = jnp.array([0.0, 0.3])
    kw = dict(n=4, k_proposers=2, samples=1_000)
    thr = engine.race(KEY, build_mask_table(specs), offs, **kw)
    before = engine.TRACE_COUNTS["race"]
    msk = engine.race(KEY, build_mask_table(specs, specialize=False),
                      offs, **kw)
    assert engine.TRACE_COUNTS["race"] - before == 1
    for k in thr:
        assert bool((thr[k] == msk[k]).all()), k
