"""Streaming engine tests (``repro.montecarlo.streaming``): sketch
correctness against exact percentiles, merge algebra, chunked-vs-
materialized identity, trial-axis sharding, and the fixed-memory scaling
contract (state size independent of trial count)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.quorum import ExplicitQuorumSystem, QuorumSpec
from repro.montecarlo import build_mask_table, engine, streaming
from repro.montecarlo.streaming import (StreamSettings, StreamSummary,
                                        bucket_index, bucket_value,
                                        sketch_bins, sketch_gamma)

KEY = jax.random.PRNGKey(0)
FFP = QuorumSpec.paper_headline(11)
FP = QuorumSpec.fast_paxos(11)
OFFS = jnp.array([0.0, 0.25], jnp.float32)


def _lat_summary(lat, precision=0.01):
    """Wrap a latency vector as an all-decided StreamSummary."""
    lat = jnp.asarray(lat, jnp.float32).reshape(1, -1)
    out = {"latency_ms": lat,
           "undecided": jnp.zeros_like(lat, bool),
           "reached_fast": jnp.ones_like(lat, bool),
           "recovery": jnp.zeros_like(lat, bool)}
    return StreamSummary.from_outcomes(out, precision)


# ---------------------------------------------------------------------------
# sketch: quantiles within the guaranteed relative error
# ---------------------------------------------------------------------------

def test_bucket_roundtrip_relative_error():
    """bucket_value(bucket_index(x)) is within ``precision`` of x across
    the covered range — the DDSketch invariant the quantile bound rests
    on."""
    for precision in (0.005, 0.01, 0.05):
        x = jnp.logspace(-1.5, 5.5, 4_000, dtype=jnp.float32)
        est = bucket_value(bucket_index(x, precision), precision)
        rel = jnp.abs(est - x) / x
        # float32 log/pow rounding eats a hair of the analytic bound
        assert float(rel.max()) < precision * 1.02, (precision,
                                                     float(rel.max()))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), size=st.integers(200, 20_000),
       scale=st.floats(0.2, 50.0))
def test_sketch_quantiles_converge_to_exact(seed, size, scale):
    """Satellite: streamed p50/p99 within the sketch's guaranteed relative
    error of exact ``jnp.percentile`` (plus one-sample rank slack)."""
    precision = 0.01
    lat = scale * jnp.exp(
        0.6 * jax.random.normal(jax.random.PRNGKey(seed), (size,))) + 0.05
    s = _lat_summary(lat, precision)
    for q in (0.5, 0.99):
        exact = float(jnp.percentile(lat, 100.0 * q))
        # the sketch uses the ceil(q*n)-th order statistic; percentile
        # interpolates — allow one rank of drift on top of the error bound
        lo = float(jnp.sort(lat)[max(0, int(np.ceil(q * size)) - 2)])
        hi = float(jnp.sort(lat)[min(size - 1, int(np.ceil(q * size)))])
        est = float(s.quantile(q)[0])
        assert (1 - 1.05 * precision) * lo <= est <= (1 + 1.05 * precision) \
            * hi, (q, est, exact, lo, hi)


def test_sketch_precision_knob_tightens_error():
    lat = jnp.exp(0.8 * jax.random.normal(KEY, (50_000,))) + 0.3
    exact = float(jnp.percentile(lat, 99.0))
    err = {}
    for precision in (0.05, 0.005):
        est = float(_lat_summary(lat, precision).quantile(0.99)[0])
        err[precision] = abs(est - exact) / exact
        assert err[precision] < precision * 1.1
    assert err[0.005] < err[0.05]


# ---------------------------------------------------------------------------
# merge algebra: exact, associative, commutative
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_sketch_merge_commutative_and_associative(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    parts = [_lat_summary(jnp.exp(jax.random.normal(k, (s,))) + 0.1)
             for k, s in zip(ks, (400, 1_300, 77))]
    a, b, c = parts
    ab, ba = a.merge(b), b.merge(a)
    # integer state merges bit-for-bit in either order
    np.testing.assert_array_equal(np.asarray(ab.hist), np.asarray(ba.hist))
    np.testing.assert_array_equal(np.asarray(ab.n_fast),
                                  np.asarray(ba.n_fast))
    assert np.allclose(np.asarray(ab.mean_ms), np.asarray(ba.mean_ms),
                       rtol=1e-6)
    abc1, abc2 = a.merge(b).merge(c), a.merge(b.merge(c))
    np.testing.assert_array_equal(np.asarray(abc1.hist),
                                  np.asarray(abc2.hist))
    np.testing.assert_array_equal(np.asarray(abc1.n_trials),
                                  np.asarray(abc2.n_trials))
    assert np.allclose(np.asarray(abc1.mean_ms), np.asarray(abc2.mean_ms),
                       rtol=1e-5)
    assert np.allclose(np.asarray(abc1.max_ms), np.asarray(abc2.max_ms))
    # merged quantiles == quantiles of the concatenated sample's sketch
    whole = _lat_summary(jnp.concatenate(
        [jnp.exp(jax.random.normal(k, (s,))) + 0.1
         for k, s in zip(ks, (400, 1_300, 77))]))
    np.testing.assert_array_equal(np.asarray(abc1.hist),
                                  np.asarray(whole.hist))


def test_merge_rejects_mismatched_precision():
    a = _lat_summary(jnp.ones((10,)), 0.01)
    b = _lat_summary(jnp.ones((10,)), 0.02)
    with pytest.raises(ValueError, match="precision"):
        a.merge(b)


# ---------------------------------------------------------------------------
# chunked streaming vs the materializing engine
# ---------------------------------------------------------------------------

def test_single_chunk_bit_identical_to_materialized():
    """Satellite: for T <= chunk the stream IS the materializing path plus
    a reduction — integer state and the max match bit-for-bit."""
    out = engine.race(KEY, build_mask_table([FFP, FP]), OFFS, n=11,
                      k_proposers=2, samples=5_000)
    ref = StreamSummary.from_outcomes(out)
    st_ = streaming.race_stream(KEY, build_mask_table([FFP, FP]), OFFS,
                                n=11, k_proposers=2, trials=5_000,
                                chunk=8_192, shard=False)
    for f in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist"):
        np.testing.assert_array_equal(np.asarray(getattr(st_, f)),
                                      np.asarray(getattr(ref, f)), f)
    np.testing.assert_array_equal(np.asarray(st_.max_ms),
                                  np.asarray(ref.max_ms))
    assert np.allclose(np.asarray(st_.mean_ms), np.asarray(ref.mean_ms),
                       rtol=1e-6)


@settings(max_examples=8, deadline=None)
@given(trials=st.integers(1, 9_000), chunk=st.integers(64, 4_096))
def test_chunk_overhang_accounting(trials, chunk):
    """Every trial is counted exactly once whatever the chunk overhang."""
    table = build_mask_table([FFP])
    st_ = streaming.fast_path_stream(jax.random.PRNGKey(trials), table,
                                     n=11, trials=trials, chunk=chunk,
                                     shard=False)
    assert int(st_.n_trials[0]) == trials
    assert int(st_.n_fast[0] + st_.n_recovery[0]
               + st_.n_undecided[0]) == trials
    assert int(np.asarray(st_.hist.sum())) == int(st_.n_decided[0])


def test_multichunk_agrees_with_materialized_statistics():
    table = build_mask_table([FFP, FP])
    st_ = streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                                trials=40_000, chunk=8_192, shard=False)
    out = engine.race(jax.random.PRNGKey(5), table, OFFS, n=11,
                      k_proposers=2, samples=40_000)
    exact = engine.summarize(out)
    got = st_.summary()
    for i in range(2):
        assert abs(float(got["p50_ms"][i]) - float(exact["p50_ms"][i])) \
            / float(exact["p50_ms"][i]) < 0.05
        assert abs(float(got["recovery_rate"][i])
                   - float(exact["recovery_rate"][i])) < 0.02


def test_stream_masked_tables_and_fused_kernel_agree():
    """The fused Pallas chunk reduction (masked tally + decide + histogram
    in one kernel pass) must match the jnp scatter path: integer state
    bit-for-bit, float reductions to tolerance."""
    grid = ExplicitQuorumSystem.grid(3).to_masks().embed(11)
    table = build_mask_table([FFP.to_masks(), grid])
    assert "q" not in table
    kw = dict(n=11, k_proposers=2, trials=6_000, chunk=2_048, shard=False)
    ref = streaming.race_stream(KEY, table, OFFS, use_kernel=False, **kw)
    ker = streaming.race_stream(KEY, table, OFFS, use_kernel=True, **kw)
    for f in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(ker, f)), f)
    assert np.allclose(np.asarray(ref.mean_ms), np.asarray(ker.mean_ms),
                       rtol=1e-5)
    assert np.allclose(np.asarray(ref.max_ms), np.asarray(ker.max_ms))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 16),
       G=st.integers(1, 5))
def test_topk_prefix_saturation_equals_full_sort(seed, n, G):
    """Satellite: for any masked table, cutting the sort to the
    ``saturation_depths`` prefix leaves ``_sat_time`` bit-identical to the
    full sort — quantized delays force ties (the top-k tie-break must match
    stable argsort order) and some arrivals sit at the crashed/lost ``inf``
    sentinel."""
    rng = np.random.default_rng(seed)
    S = 64
    w = rng.integers(0, 4, size=(G, n)).astype(np.float32)
    # mix of saturable and unsaturable rows (threshold above total weight)
    t = np.maximum(1.0, rng.integers(1, max(2, int(w.sum(-1).max()) + 3),
                                     size=(G,))).astype(np.float32)
    x = np.floor(rng.exponential(4.0, size=(S, n)) * 4.0) / 4.0   # ties
    x[rng.random((S, n)) < 0.15] = float(engine.BIG)   # crashed / lost
    xw, tw = jnp.asarray(w), jnp.asarray(t)
    xj = jnp.asarray(x, jnp.float32)

    tbl = {"p1_w": xw[None], "p1_t": tw[None], "p2c_w": xw[None],
           "p2c_t": tw[None], "p2f_w": xw[None], "p2f_t": tw[None]}
    k = engine.saturation_depths(tbl)[0]
    srt_full, perm_full = engine._topk_ascending(xj, None)
    srt_k, perm_k = engine._topk_ascending(xj, k)
    full = engine._sat_time(srt_full, perm_full, xw, tw)
    pref = engine._sat_time(srt_k, perm_k, xw, tw)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(pref))
    # top-k prefix itself matches the stable full sort element-for-element
    np.testing.assert_array_equal(np.asarray(srt_full[:, :k]),
                                  np.asarray(srt_k))
    np.testing.assert_array_equal(np.asarray(perm_full[:, :k]),
                                  np.asarray(perm_k))


def test_sortfree_card_streams_bit_identical_to_full_sort():
    """Acceptance gate: on a cardinality batch, the sort-free streamed
    lowering (k_max="auto" — shared-column order-statistic reductions, no
    per-system sorted gathers) produces bit-identical integer state and
    histogram vs the retained full-sort reference path (k_max=None) on all
    three drivers."""
    table = build_mask_table([FFP, FP, QuorumSpec.majority_fast(11)])
    assert "q" in table
    kw = dict(n=11, trials=20_000, chunk=4_096, shard=False)
    fields = ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist")
    for name, call in (
        ("race", lambda km: streaming.race_stream(
            KEY, table, OFFS, n=11, k_proposers=2, trials=20_000,
            chunk=4_096, shard=False, k_max=km)),
        ("fast", lambda km: streaming.fast_path_stream(KEY, table,
                                                       k_max=km, **kw)),
        ("classic", lambda km: streaming.classic_path_stream(KEY, table,
                                                             k_max=km, **kw)),
    ):
        ref, new = call(None), call("auto")
        for f in fields:
            np.testing.assert_array_equal(np.asarray(getattr(new, f)),
                                          np.asarray(getattr(ref, f)),
                                          f"{name}.{f}")
        np.testing.assert_array_equal(np.asarray(new.max_ms),
                                      np.asarray(ref.max_ms), name)
        assert np.allclose(np.asarray(new.mean_ms), np.asarray(ref.mean_ms),
                           rtol=1e-5, equal_nan=True)


def test_recovery_rule_streamed_parity_and_invariants():
    """The collision-recovery rule as a dispatch axis: the entry condition
    (hence every count) is rule-invariant, fast-path latencies are
    bit-identical (recovery only re-prices the classic leg), the
    histograms DO move, and the sort-free lowering stays bit-identical to
    the full-sort reference under both rules."""
    table = build_mask_table([FFP, FP])
    kw = dict(n=11, k_proposers=2, trials=20_000, chunk=4_096, shard=False)
    sc = streaming.race_stream(KEY, table, OFFS, **kw)
    su = streaming.race_stream(KEY, table, OFFS,
                               recovery="uncoordinated", **kw)
    for f in ("n_trials", "n_fast", "n_recovery", "n_undecided"):
        np.testing.assert_array_equal(np.asarray(getattr(sc, f)),
                                      np.asarray(getattr(su, f)), f)
    assert not np.array_equal(np.asarray(sc.hist), np.asarray(su.hist))
    for mode in ("coordinated", "uncoordinated"):
        ref = streaming.race_stream(KEY, table, OFFS, k_max=None,
                                    recovery=mode, **kw)
        new = streaming.race_stream(KEY, table, OFFS, k_max="auto",
                                    recovery=mode, **kw)
        np.testing.assert_array_equal(np.asarray(new.hist),
                                      np.asarray(ref.hist), mode)

    # materializing path: the fast-path latency samples are bit-identical
    # across rules; only recovered trials move
    oc = engine.race(KEY, table, OFFS, n=11, k_proposers=2, samples=4_000)
    ou = engine.race(KEY, table, OFFS, n=11, k_proposers=2, samples=4_000,
                     recovery="uncoordinated")
    np.testing.assert_array_equal(np.asarray(oc["reached_fast"]),
                                  np.asarray(ou["reached_fast"]))
    fast = np.asarray(oc["reached_fast"])
    np.testing.assert_array_equal(np.asarray(oc["latency_ms"])[fast],
                                  np.asarray(ou["latency_ms"])[fast])

    with pytest.raises(ValueError, match="unknown recovery rule"):
        streaming.race_stream(KEY, table, OFFS, recovery="oracle", **kw)
    with pytest.raises(ValueError, match="unknown recovery rule"):
        engine.race(KEY, table, OFFS, n=11, k_proposers=2, samples=100,
                    recovery="oracle")


SLOT_HIST_CASES = ([(g, c, "spread") for g in (1, 11, 66)
                    for c in (1, 777, 4_096)]
                   + [(g, 4_096, "one_bin") for g in (1, 66)])


@pytest.mark.parametrize("groups,trials,fill", SLOT_HIST_CASES)
def test_slot_hist_onehot_lowering_matches_scatter(groups, trials, fill):
    """The TPU lowering of ``_slot_hist`` (one-hot contraction over trials)
    gives the scatter lowering's integers bit for bit, called directly on
    the CPU: buckets at 0, at the last bucket and off the grid, slots
    including the dropped padding slot, and every trial in one bin (the
    whole chunk summed exactly)."""
    nb, ns = 37, 12
    if fill == "one_bin":
        bkey = jnp.full((trials, groups), nb - 1, jnp.int32)
        slot = jnp.zeros((trials,), jnp.int32)
    else:
        k1, k2 = jax.random.split(jax.random.PRNGKey(trials * 100 + groups))
        edges = jnp.array([0, nb - 1, nb, nb + 5], jnp.int32)
        raw = jax.random.randint(k1, (trials, groups), 0, nb + edges.size)
        bkey = jnp.where(raw < nb, raw, edges[jnp.clip(raw - nb, 0, 3)])
        slot = jax.random.randint(k2, (trials,), 0, ns + 2)  # ns: padding
    kw = dict(n_buckets=nb, n_slots=ns)
    want = streaming._slot_hist_scatter(bkey, slot, **kw)
    got = streaming._slot_hist_onehot(bkey, slot, **kw)
    assert got.shape == want.shape == (groups, ns, nb)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    kept = ((bkey < nb) & (slot < ns)[:, None]).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(want.sum(axis=(1, 2))),
                                  np.asarray(kept))
    if fill == "one_bin":
        assert int(want[:, 0, nb - 1].min()) == trials


def test_slot_hist_lowers_to_scatter_on_cpu():
    """On the CPU the dispatch takes the scatter: no contraction is
    compiled, so no one-hot is written out."""
    txt = jax.jit(lambda b, s: streaming._slot_hist(
        b, s, n_buckets=1039, n_slots=12)).lower(
        jnp.zeros((1_024, 66), jnp.int32),
        jnp.zeros((1_024,), jnp.int32)).compile().as_text()
    assert "scatter" in txt
    assert "dot(" not in txt and "convolution(" not in txt


@pytest.fixture
def slot_hist_onehot(monkeypatch):
    """Send the stream's histograms through the TPU lowering, for one test:
    the compiled ``_stream`` programs are dropped before and after so no
    other test runs a program traced with it."""
    traced = []

    def onehot(bkey, slot, *, n_buckets, n_slots):
        traced.append(bkey.shape)
        return streaming._slot_hist_onehot(bkey, slot, n_buckets=n_buckets,
                                           n_slots=n_slots)
    streaming._stream.clear_cache()
    monkeypatch.setattr(streaming, "_slot_hist", onehot)
    yield traced
    monkeypatch.undo()
    streaming._stream.clear_cache()


@pytest.mark.parametrize("recovery", ["coordinated", "uncoordinated"])
def test_onehot_slot_hist_stream_bit_identical_to_full_sort(
        slot_hist_onehot, recovery):
    """The race stream with its histograms counted by the one-hot
    contraction (the TPU lowering) stays bit-identical to the full-sort
    reference path, as the scatter lowering is
    (``test_sortfree_card_streams_bit_identical_to_full_sort``)."""
    table = build_mask_table([FFP, FP, QuorumSpec.majority_fast(11)])
    kw = dict(n=11, k_proposers=2, trials=20_000, chunk=1_024, shard=False,
              recovery=recovery)
    ref = streaming.race_stream(KEY, table, OFFS, k_max=None, **kw)
    new = streaming.race_stream(KEY, table, OFFS, k_max="auto", **kw)
    for f in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist",
              "max_ms"):
        np.testing.assert_array_equal(np.asarray(getattr(new, f)),
                                      np.asarray(getattr(ref, f)), f)
    assert np.allclose(np.asarray(new.mean_ms), np.asarray(ref.mean_ms),
                       rtol=1e-5, equal_nan=True)
    assert len(slot_hist_onehot) == 2       # FH and RH both went through it


def test_recovery_rule_fused_kernel_agrees():
    """The fused Pallas lowering under the uncoordinated rule (recovery
    saturation fed the p2f masks) matches the jnp scatter path."""
    grid = ExplicitQuorumSystem.grid(3).to_masks().embed(11)
    table = build_mask_table([FFP.to_masks(), grid])
    assert "q" not in table
    kw = dict(n=11, k_proposers=2, trials=6_000, chunk=2_048, shard=False,
              recovery="uncoordinated")
    ref = streaming.race_stream(KEY, table, OFFS, use_kernel=False, **kw)
    ker = streaming.race_stream(KEY, table, OFFS, use_kernel=True, **kw)
    for f in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(ker, f)), f)
    assert np.allclose(np.asarray(ref.mean_ms), np.asarray(ker.mean_ms),
                       rtol=1e-5)


def test_k_max_below_saturation_depth_rejected():
    """An explicit k_max below the table's saturation depths would silently
    change semantics — the driver must refuse it."""
    table = build_mask_table([FFP, FP])
    with pytest.raises(ValueError, match="saturation depths"):
        streaming.fast_path_stream(KEY, table, n=11, trials=20_000,
                                   chunk=4_096, shard=False, k_max=(1, 1, 1))


def test_stream_single_compile_per_table_shape():
    """TRACE_COUNTS invariant: one compile per (table shape, chunk count) —
    different trial counts with the same chunking, different keys, and
    different same-shape tables all re-enter it (trials and table contents
    are traced; only the scan length is static)."""
    table = build_mask_table([FFP, FP])
    streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                          trials=9_000, chunk=2_048, shard=False)
    before = dict(engine.TRACE_COUNTS)
    # 8_300..10_240 trials all scan 5 chunks of 2_048
    streaming.race_stream(jax.random.PRNGKey(1), table, OFFS, n=11,
                          k_proposers=2, trials=10_000, chunk=2_048,
                          shard=False)
    streaming.race_stream(KEY, build_mask_table([FP, FFP]), OFFS, n=11,
                          k_proposers=2, trials=8_500, chunk=2_048,
                          shard=False)
    assert engine.TRACE_COUNTS == before


def test_stream_state_size_independent_of_trials():
    """The fixed-memory contract at the state level: summary leaves have
    identical shapes at 3k and 300k trials (only chunk size matters)."""
    table = build_mask_table([FFP])
    small = streaming.fast_path_stream(KEY, table, n=11, trials=3_000,
                                       chunk=1_024, shard=False)
    big = streaming.fast_path_stream(KEY, table, n=11, trials=300_000,
                                     chunk=1_024, shard=False)
    shapes = lambda s: [leaf.shape for leaf in jax.tree_util.tree_leaves(s)]
    assert shapes(small) == shapes(big)
    assert int(big.n_trials[0]) == 300_000


def test_classic_path_stream_semantics():
    table = build_mask_table([FFP])
    st_ = streaming.classic_path_stream(KEY, table, n=11, trials=5_000,
                                        chunk=2_048, shard=False)
    assert int(st_.n_fast[0]) == 0
    assert int(st_.n_recovery[0]) == 5_000
    fast = streaming.fast_path_stream(KEY, table, n=11, trials=5_000,
                                      chunk=2_048, shard=False)
    # classic adds the client->leader relay hop
    assert float(st_.quantile(0.5)[0]) > float(fast.quantile(0.5)[0])


def test_empty_summary_is_nan_rates_zero():
    s = StreamSummary.zeros(2)
    d = s.summary()
    assert np.isnan(np.asarray(d["p50_ms"])).all()
    assert np.isnan(np.asarray(d["mean_ms"])).all()
    assert float(d["fast_rate"][0]) == 0.0


# ---------------------------------------------------------------------------
# sharding over the trial axis (exercised for real in the CI multi-device
# job via XLA_FLAGS=--xla_force_host_platform_device_count=8)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (run under "
                           "--xla_force_host_platform_device_count)")
def test_sharded_stream_counts_exact_and_stats_agree():
    table = build_mask_table([FFP, FP])
    trials = 30_011                      # deliberately not divisible
    sh = streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                               trials=trials, chunk=2_048, shard=True)
    assert [int(x) for x in sh.n_trials] == [trials, trials]
    un = streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                               trials=trials, chunk=2_048, shard=False)
    for i in range(2):
        assert abs(float(sh.quantile(0.5)[i]) - float(un.quantile(0.5)[i])) \
            / float(un.quantile(0.5)[i]) < 0.05
        assert abs(float(sh.summary()["recovery_rate"][i])
                   - float(un.summary()["recovery_rate"][i])) < 0.02


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (run under "
                           "--xla_force_host_platform_device_count)")
def test_sharded_fast_path_stream_exact_totals():
    table = build_mask_table([FFP])
    st_ = streaming.fast_path_stream(KEY, table, n=11, trials=10_001,
                                     chunk=512, shard=True)
    assert int(st_.n_trials[0]) == 10_001
    assert int(st_.n_fast[0]) == 10_001       # no loss model -> all decide


# ---------------------------------------------------------------------------
# acceptance: 10^7 trials, n=11, fixed memory, through the Experiment API
# ---------------------------------------------------------------------------

def test_experiment_ten_million_trials_fixed_memory():
    """The ISSUE acceptance criterion: an n=11 system streams 10^7 trials
    through ``Experiment`` with a fixed-size state, and the streamed p50/
    p99 sit within the sketch's documented error of exact percentiles
    measured on a materialized slice of the same workload."""
    from repro.api import Experiment, Workload
    exp = Experiment(systems=[FFP], workload=Workload.conflict_free(),
                     trials=10_000_000,
                     stream=streaming.StreamSettings(chunk=262_144),
                     compute_fault_tolerance=False)
    r = exp.run("montecarlo")
    state = r.stream
    assert int(state.n_trials[0]) == 10_000_000
    assert state.hist.shape == (1, sketch_bins(exp.stream.precision))
    # exact reference: a materialized 200k sample of the same distribution
    exact = engine.summarize(engine.fast_path(
        jax.random.PRNGKey(17), build_mask_table([FFP]), n=11,
        samples=200_000))
    for q in ("p50_ms", "p99_ms"):
        got, ref = float(r.summary[q][0]), float(exact[q][0])
        # sketch precision + cross-sample Monte-Carlo noise at 200k
        assert abs(got - ref) / ref < exp.stream.precision + 0.02, \
            (q, got, ref)


# ---------------------------------------------------------------------------
# RNG fold-in domains: device keys must never collide with chunk keys
# ---------------------------------------------------------------------------

def test_device_and_chunk_fold_in_domains_disjoint():
    """Regression (ISSUE 7 satellite): the sharded per-device keys used to
    be ``fold_in(key, 0x5eed + d)`` — the same fold-in space as the
    unsharded per-chunk keys ``fold_in(key, c)``, so chunk 0x5eed + d of a
    long stream replayed device d's draws.  The two-level derivation
    ``fold_in(fold_in(key, DEVICE_FOLD_DOMAIN), d)`` must be disjoint from
    every chunk key for n_chunks up to 2^20."""
    key = jax.random.PRNGKey(0)
    n_chunks, n_dev = 1 << 20, 4_096
    chunk_keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(
        jnp.arange(n_chunks, dtype=jnp.int32))
    dev_base = jax.random.fold_in(
        key, jnp.int32(streaming.DEVICE_FOLD_DOMAIN))
    dev_keys = jax.vmap(lambda d: jax.random.fold_in(dev_base, d))(
        jnp.arange(n_dev, dtype=jnp.int32))

    def pack(ks):                         # (N, 2) uint32 -> (N,) uint64
        a = np.asarray(ks).astype(np.uint64)
        return (a[:, 0] << np.uint64(32)) | a[:, 1]

    assert np.intersect1d(pack(chunk_keys), pack(dev_keys)).size == 0
    # and the OLD single-level scheme demonstrably collided: device 0's
    # key WAS chunk key 0x5eed
    old_dev0 = jax.random.fold_in(key, jnp.int32(0x5eed) + jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(old_dev0),
                                  np.asarray(chunk_keys[0x5eed]))


# ---------------------------------------------------------------------------
# mesh resolution: loud single-device fallback, explicit meshes honored
# ---------------------------------------------------------------------------

def test_resolve_mesh_single_device_warns_or_shards():
    import warnings as _warnings
    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        mesh = streaming._resolve_mesh(True)
    if len(jax.devices()) == 1:
        assert mesh is None
        assert any("only 1 device" in str(x.message) for x in w), \
            [str(x.message) for x in w]
    else:
        assert mesh is not None
        assert not w, [str(x.message) for x in w]
    # shard=False / None stay silent and unsharded
    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        assert streaming._resolve_mesh(False) is None
        assert streaming._resolve_mesh(None) is None
    assert not w


def test_explicit_single_device_mesh_honored():
    """A deliberately-passed 1-device Mesh must run the sharded (collective)
    code path, not silently degrade to unsharded — multi-process workers
    depend on every process entering the same psum."""
    from repro.parallel import sharding as psharding
    mesh = psharding.trial_mesh(jax.devices()[:1])
    assert streaming._resolve_mesh(mesh) is mesh
    table = build_mask_table([FFP, FP])
    st_ = streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                                trials=10_007, chunk=2_048, shard=mesh)
    assert [int(x) for x in st_.n_trials] == [10_007, 10_007]
    un = streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                               trials=10_007, chunk=2_048, shard=False)
    # different (device-domain) key stream, same distribution
    for i in range(2):
        assert abs(float(st_.quantile(0.5)[i]) - float(un.quantile(0.5)[i])) \
            / float(un.quantile(0.5)[i]) < 0.05


# ---------------------------------------------------------------------------
# trials < ndev: empty devices contribute the exact zeros identity
# ---------------------------------------------------------------------------

def test_zero_summary_is_exact_merge_identity():
    """zeros() must be the identity of the merge algebra — counts/hist
    unchanged, max_ms not poisoned by the -inf init, mean not NaN — because
    on a wide mesh with trials < ndev the trailing devices contribute
    exactly this state to the cross-device psum/pmax."""
    table = build_mask_table([FFP, FP])
    st_ = streaming.race_stream(KEY, table, OFFS, n=11, k_proposers=2,
                                trials=4_000, chunk=1_024, shard=False)
    for merged in (st_.merge(StreamSummary.zeros(2, st_.precision)),
                   StreamSummary.zeros(2, st_.precision).merge(st_)):
        for f in ("n_trials", "n_fast", "n_recovery", "n_undecided", "hist"):
            np.testing.assert_array_equal(np.asarray(getattr(merged, f)),
                                          np.asarray(getattr(st_, f)), f)
        np.testing.assert_array_equal(np.asarray(merged.max_ms),
                                      np.asarray(st_.max_ms))
        assert np.isfinite(np.asarray(merged.mean_ms)).all()
        np.testing.assert_allclose(np.asarray(merged.mean_ms),
                                   np.asarray(st_.mean_ms), rtol=1e-6)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (run under "
                           "--xla_force_host_platform_device_count)")
def test_sharded_trials_below_device_count():
    """trials < ndev leaves devices empty: their short-circuited zero
    contribution must keep the merged summary exact (no -inf/NaN leakage)."""
    ndev = len(jax.devices())
    table = build_mask_table([FFP])
    st_ = streaming.fast_path_stream(KEY, table, n=11, trials=ndev - 1,
                                     chunk=64, shard=True)
    assert int(st_.n_trials[0]) == ndev - 1
    assert int(st_.n_fast[0]) == ndev - 1
    assert np.isfinite(np.asarray(st_.max_ms)).all()
    assert np.isfinite(np.asarray(st_.mean_ms)).all()
    assert int(np.asarray(st_.hist).sum()) == ndev - 1


# ---------------------------------------------------------------------------
# _resolve_k_sat edge cases: clip-vs-validate order pinned
# ---------------------------------------------------------------------------

def test_resolve_k_sat_clips_above_n_after_validation():
    """Components > n pass depth validation first and only then clip to n
    — an explicit (100, 100, 100) is a valid 'everything' request."""
    table = build_mask_table([FFP, FP])
    assert streaming._resolve_k_sat(table, (100, 100, 100), 11) \
        == (11, 11, 11)


def test_resolve_k_sat_validates_before_clipping():
    """The order is observable below 1: on a depth-(1,1,1) table a request
    of (0,0,0) must RAISE (validate first) — clip-first would silently lift
    it to the legal (1,1,1)."""
    table = build_mask_table([QuorumSpec(1, 1, 1, 1)])
    assert engine.saturation_depths(table) == (1, 1, 1)
    with pytest.raises(ValueError, match="saturation depths"):
        streaming._resolve_k_sat(table, (0, 0, 0), 1)
    # and the clipped legal request still resolves
    assert streaming._resolve_k_sat(table, (5, 5, 5), 1) == (1, 1, 1)


def test_resolve_k_sat_int_below_depths_raises():
    table = build_mask_table([FFP, FP])     # q2f depths reach 9 (FP)
    with pytest.raises(ValueError, match="saturation depths"):
        streaming._resolve_k_sat(table, 2, 11)


def test_resolve_k_sat_auto_on_mixed_table():
    """'auto' on a mixed cardinality+masked batch (no "q" specialization)
    must still equal the table's saturation depths."""
    grid = ExplicitQuorumSystem.grid(3).to_masks().embed(11)
    table = build_mask_table([FFP.to_masks(), grid])
    assert "q" not in table
    assert streaming._resolve_k_sat(table, "auto", 11) \
        == engine.saturation_depths(table)


# ---------------------------------------------------------------------------
# StreamSettings: validated at construction, flat keys round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flat, error", [
    ({"chunk": 0}, "chunk must be >= 1"),
    ({"precision": 0.5}, "precision"),
    ({"k_max": "deep"}, "k_max must be"),
    ({"k_max": (9, 3)}, "k_max must be"),
    ({}, None),
    ({"chunk": 16_384, "precision": 0.02, "shard": False,
      "use_kernel": True, "k_max": [9, 3, 7]}, None),
    ({"chunk": None, "precision": None, "k_max": None}, None),
])
def test_stream_settings_validate_and_round_trip(flat, error):
    """A bad chunk, precision or k_max is refused when the settings are
    built, not when a stream first runs; a good flat dict lifts over the
    base (a null chunk/precision keeps it), leaves its other keys alone,
    and ``to_flat`` / ``from_flat`` round-trip to the same settings."""
    base = StreamSettings(chunk=8_192, shard=False)
    if error is not None:
        with pytest.raises(ValueError, match=error):
            StreamSettings.from_flat(dict(flat, seed=3), base)
        return
    s, rest = StreamSettings.from_flat(dict(flat, seed=3), base)
    assert rest == {"seed": 3}
    want = dict(base.to_flat(), **{
        k: tuple(v) if isinstance(v, list) else v for k, v in flat.items()
        if v is not None or k == "k_max"})
    assert s.to_flat() == want
    assert StreamSettings.from_flat(s.to_flat())[0] == s
    assert StreamSettings(**s.to_flat()) == s
    hash(s)                                  # usable as a search key
