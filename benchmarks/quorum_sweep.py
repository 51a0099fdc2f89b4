"""Beyond-paper: the full FFP-valid quorum space on n=11 as a *streamed*
Pareto frontier.

The paper (§5) gives two example points in the (q1, q2c, q2f) tradeoff
space.  We enumerate the *whole* space permitted by Eqs. 13/14 (271
systems at n=11 — ``repro.frontier.families.cardinality_family``) and
score every one through the streaming engine (``repro.frontier.score``):
one ``fast_path_stream`` pass and one ``race_stream`` pass over the whole
batch — 10^7 trials each in the full run (10^6 under ``--smoke``), fixed
memory, common random numbers, ONE compile per engine path — extracting
the six frontier axes a deployment cares about:

  fast_p50_ms    conflict-free fast-path median
  race_p999_ms   p99.9 commit latency under a 2-way race at Δ=0.2 ms —
                 the tail axis only streamed trial counts make meaningful
  p_recovery     P(coordinated recovery | race)
  ft_fast / ft_phase1 / ft_classic   per-phase crash budgets

The Pareto-optimal set under ``repro.frontier.pareto`` (epsilon ties
matched to sketch precision) is the flexibility the paper's relaxation
buys: Fast Paxos admits exactly one point (q1=q2c=6, q2f=9).

The sweep asserts the single-compile property (``engine.TRACE_COUNTS``),
agreement of the streamed numbers with the legacy per-spec reference
below (different implementation, different PRNG stream), and that the
legacy quorum-size-minimal set is contained in the scored frontier.

``run_relaxed`` (the ``relaxed`` section of ``benchmarks.run``) widens the
space to Relaxed Paxos (arXiv 2203.03058): the 125 relaxed-valid /
FFP-invalid triples at n=11 join the 271 FFP systems on ONE streamed
frontier, scored under both collision-recovery rules (coordinated q2c
commit vs the uncoordinated q2f rule of arXiv 1710.08047).  It asserts at
least one relaxed system survives to the joint frontier, that the second
recovery rule costs exactly one extra ``race_stream`` compile (the fast
path is rule-invariant and shares its compile), and that fast-path
latencies are bit-identical across rules.

Usage:  PYTHONPATH=src python -m benchmarks.quorum_sweep [--smoke]
                                                         [--relaxed]
"""
from __future__ import annotations

import argparse
import os
import time
from typing import List, Tuple

import jax

# Join a multi-process grid BEFORE anything touches the jax backend: both
# the gloo CPU-collectives selection and jax.distributed.initialize only
# take effect pre-backend.  No-op without the REPRO_* launch env.
if os.environ.get("REPRO_COORDINATOR"):
    from repro.parallel import distributed as _distributed
    _distributed.initialize()

import jax.numpy as jnp
import numpy as np

from repro.core.quorum import QuorumSpec, ffp_card_ok
from repro.frontier import cardinality_family, relaxed_family, score_systems
from repro.montecarlo import engine

N = 11
TRIALS = 10_000_000
TRIALS_SMOKE = 1_000_000
CHUNK = 16_384
DELTA_MS = 0.2
LEGACY_SAMPLES = 50_000


# ---------------------------------------------------------------------------
# Independent per-spec reference: the pre-refactor static-threshold
# implementation (one jit per spec, python-int order statistics).  Kept here
# verbatim so the streamed engine is checked against a *different* code path,
# not a shim that now shares its internals.
# ---------------------------------------------------------------------------

def _legacy_one_way(key, shape, base=0.25, mu=-1.20, sigma=0.55):
    return base + jnp.exp(mu + sigma * jax.random.normal(key, shape))


def _legacy_fast_p50(key, n: int, q2f: int, samples: int) -> float:
    k1, k2 = jax.random.split(key)
    d = _legacy_one_way(k1, (samples, n)) + _legacy_one_way(k2, (samples, n))
    return float(jnp.median(jnp.sort(d, axis=-1)[:, q2f - 1]))


def _legacy_recovery_prob(key, spec: QuorumSpec, delta_ms: float,
                          samples: int) -> float:
    kA, kB = jax.random.split(key)
    tA = _legacy_one_way(kA, (samples, spec.n))
    tB = delta_ms + _legacy_one_way(kB, (samples, spec.n))
    votes = (tB < tA).astype(jnp.int32)
    b_cnt = votes.sum(axis=-1)
    a_cnt = spec.n - b_cnt
    return float((~((a_cnt >= spec.q2f) | (b_cnt >= spec.q2f))).mean())


def enumerate_valid(n: int = N) -> List[QuorumSpec]:
    """Brute-force triple loop over Eqs. 13/14 — the independent check on
    ``families.cardinality_family``'s enumeration."""
    out = []
    for q1 in range(1, n + 1):
        for q2c in range(1, n + 1):
            for q2f in range(1, n + 1):
                if ffp_card_ok(n, q1, q2c, q2f):
                    out.append(QuorumSpec(n, q1, q2c, q2f))
    return out


def minimal_frontier(specs: List[QuorumSpec]) -> List[QuorumSpec]:
    """Legacy per-spec reference: drop specs dominated in (q1, q2c, q2f) —
    larger quorums are never better on any axis we score.  Retained as the
    cross-check the scored frontier's membership is validated against."""
    keep = []
    for s in specs:
        if not any(o.q1 <= s.q1 and o.q2c <= s.q2c and o.q2f <= s.q2f
                   and (o.q1, o.q2c, o.q2f) != (s.q1, s.q2c, s.q2f)
                   for o in specs):
            keep.append(s)
    return keep


def run(quick: bool = False, seed: int = 0, shard=True):
    trials = TRIALS_SMOKE if quick else TRIALS
    legacy_samples = 5_000 if quick else LEGACY_SAMPLES

    members = cardinality_family(N)
    specs = [m.system for m in members]
    # family generator vs the independent brute-force enumeration
    assert ({(s.q1, s.q2c, s.q2f) for s in specs}
            == {(s.q1, s.q2c, s.q2f) for s in enumerate_valid(N)})

    rows: List[Tuple[str, float]] = [
        ("sweep.n_valid_configs", len(members)),
        ("sweep.trials", trials),
    ]

    # -- the entire space in two streamed engine calls (one compile each) --
    t0 = dict(engine.TRACE_COUNTS)
    wall0 = time.perf_counter()
    result = score_systems(members, trials=trials, chunk=CHUNK,
                           delta_ms=DELTA_MS, shard=shard, seed=seed)
    jax.block_until_ready(result.streams["race"].hist)
    wall = time.perf_counter() - wall0
    traced = {k: engine.TRACE_COUNTS[k] - t0[k] for k in t0}
    # exactly one compile per stream path, and both on the sort-free
    # lowering — a second trace (or a silent fall-back to the full-sort
    # path) is a perf regression the trials/sec row would only show late
    for k in ("fast_path_stream", "race_stream",
              "fast_path_stream_sortfree", "race_stream_sortfree"):
        assert traced[k] == 1, (
            f"expected exactly one {k} trace for {len(members)} specs, got "
            f"{traced[k]} (all deltas: { {a: b for a, b in traced.items() if b} })")
    rows.append(("sweep.engine_compiles",
                 traced["fast_path_stream"] + traced["race_stream"]))
    # streamed throughput across both passes (fast + race trials / wall);
    # _is_throughput in check_regression treats this as higher-is-better
    rows.append(("sweep.trials_per_sec", 2.0 * trials / wall))

    mask = np.asarray(result.mask)
    rows.append(("sweep.n_frontier_systems", int(mask.sum())))
    for i in result.frontier_indices:
        row = result.row(i)
        tag = result.labels[i]
        for axis in ("fast_p50_ms", "race_p999_ms", "p_recovery",
                     "ft_fast", "ft_phase1", "ft_classic"):
            rows.append((f"sweep.[{tag}].{axis}", row[axis]))

    # -- streamed vs independent per-spec reference (Monte-Carlo + sketch
    # tolerance): different implementation, different PRNG stream, so
    # agreement is a real check on the engine, not a tautology.
    k_check = jax.random.PRNGKey(1234)
    # difference of two independent p-estimates has sd <= sqrt(0.5/samples)
    # (the legacy sample count dominates); 4.5 sigma keeps the check
    # meaningful without making the CI smoke job flaky
    tol_rec = 4.5 * (0.5 / legacy_samples) ** 0.5
    front = result.frontier_indices
    for i in (front[0], front[len(front) // 2], front[-1]):
        s = specs[i]
        row = result.row(i)
        old_p50 = _legacy_fast_p50(jax.random.fold_in(k_check, i),
                                   s.n, s.q2f, legacy_samples)
        old_rec = _legacy_recovery_prob(jax.random.fold_in(k_check, 100 + i),
                                        s, DELTA_MS, legacy_samples)
        assert abs(old_p50 - row["fast_p50_ms"]) < 0.05, (s, old_p50, row)
        assert abs(old_rec - row["p_recovery"]) < tol_rec, (s, old_rec, row)
    rows.append(("sweep.streamed_vs_perspec_checked", 3))

    # -- membership cross-check: every quorum-size-minimal spec is
    # undominated on the scored axes (one spec per q1; see tests/
    # test_frontier.py for the fixed-seed anchor of the full set)
    minimal = {(s.q1, s.q2c, s.q2f) for s in minimal_frontier(specs)}
    scored = {(specs[i].q1, specs[i].q2c, specs[i].q2f) for i in front}
    assert minimal <= scored, sorted(minimal - scored)
    rows.append(("sweep.minimal_subset_of_frontier", len(minimal)))

    # sanity: fast-path latency is monotone in q2f on the frontier
    by_q2f = sorted(front, key=lambda i: specs[i].q2f)
    lats = [result.row(i)["fast_p50_ms"] for i in by_q2f]
    assert all(a <= b + 0.05 for a, b in zip(lats, lats[1:])), lats
    return rows


def run_relaxed(quick: bool = False, seed: int = 0, shard=True):
    """Joint FFP + Relaxed frontier under both collision-recovery rules."""
    trials = TRIALS_SMOKE if quick else TRIALS

    ffp = cardinality_family(N)
    relaxed = relaxed_family(N)
    members = ffp + relaxed
    ffp_count = len(ffp)
    rows: List[Tuple[str, float]] = [
        ("relaxed.n_valid_configs", len(relaxed)),
        ("relaxed.n_joint_systems", len(members)),
        ("relaxed.trials", trials),
    ]

    # -- coordinated rule: the whole joint space, one compile per path --
    t0 = dict(engine.TRACE_COUNTS)
    wall0 = time.perf_counter()
    coord = score_systems(members, trials=trials, chunk=CHUNK,
                          delta_ms=DELTA_MS, shard=shard, seed=seed)
    jax.block_until_ready(coord.streams["race"].hist)
    wall = time.perf_counter() - wall0
    traced = {k: engine.TRACE_COUNTS[k] - t0[k] for k in t0}
    for k in ("fast_path_stream", "race_stream",
              "fast_path_stream_sortfree", "race_stream_sortfree"):
        assert traced[k] == 1, (
            f"joint sweep expected one {k} trace, got {traced[k]}")
    rows.append(("relaxed.engine_compiles",
                 traced["fast_path_stream"] + traced["race_stream"]))
    rows.append(("relaxed.trials_per_sec", 2.0 * trials / wall))

    front = coord.frontier_indices
    on_front = [i for i in front if i >= ffp_count]
    rows.append(("relaxed.n_frontier_systems", len(front)))
    rows.append(("relaxed.n_relaxed_on_frontier", len(on_front)))
    # the paper-level claim: relaxing quorum intersection buys points FFP
    # cannot express — at least one survives the joint Pareto reduction
    assert on_front, (
        "no relaxed-valid/FFP-invalid system on the joint frontier")
    for i in on_front[:3]:
        row = coord.row(i)
        tag = coord.labels[i]
        for axis in ("fast_p50_ms", "race_p999_ms", "p_recovery",
                     "ft_fast", "ft_phase1", "ft_classic"):
            rows.append((f"relaxed.[{tag}].{axis}", row[axis]))

    # -- uncoordinated rule: same batch, only the race pass re-lowers --
    t1 = dict(engine.TRACE_COUNTS)
    uncoord = score_systems(members, trials=trials, chunk=CHUNK,
                            delta_ms=DELTA_MS, shard=shard, seed=seed,
                            recovery="uncoordinated")
    jax.block_until_ready(uncoord.streams["race"].hist)
    traced = {k: engine.TRACE_COUNTS[k] - t1[k] for k in t1}
    assert traced["race_stream"] == 1, (
        f"uncoordinated rule expected one race_stream trace, got "
        f"{traced['race_stream']}")
    assert traced["fast_path_stream"] == 0, (
        f"fast path is recovery-invariant but re-traced "
        f"{traced['fast_path_stream']} times")
    rows.append(("relaxed.uncoord_engine_compiles", traced["race_stream"]))

    # the fast path (and the recovery *entry* condition) must not depend on
    # the rule; only the recovery tail may move
    cv, uv = np.asarray(coord.values), np.asarray(uncoord.values)
    names = list(coord.axis_names)
    assert np.array_equal(cv[:, names.index("fast_p50_ms")],
                          uv[:, names.index("fast_p50_ms")])
    assert np.array_equal(cv[:, names.index("p_recovery")],
                          uv[:, names.index("p_recovery")])
    rows.append(("relaxed.rule_invariants_checked", 2))

    # tail reprice: the uncoordinated rule commits recovery at q2f instead
    # of q2c — report the joint-frontier witness under both rules
    i = on_front[0]
    rows.append((f"relaxed.[{coord.labels[i]}].race_p999_ms.uncoordinated",
                 uncoord.row(i)["race_p999_ms"]))
    return rows


def main(quick: bool = False, shard=True):
    rows = run(quick, shard=shard)
    if jax.process_index() == 0:        # one copy of the CSV per grid
        for name, val in rows:
            print(f"{name},{val:.6g}")
    return rows


def main_relaxed(quick: bool = False, shard=True):
    rows = run_relaxed(quick, shard=shard)
    if jax.process_index() == 0:
        for name, val in rows:
            print(f"{name},{val:.6g}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="10^6 streamed trials instead of 10^7; asserts "
                         "and frontier membership only")
    ap.add_argument("--relaxed", action="store_true",
                    help="also run the joint FFP + Relaxed Paxos frontier "
                         "under both collision-recovery rules")
    ap.add_argument("--shard", action="store_true",
                    help="join the multi-process grid configured via "
                         "REPRO_COORDINATOR/REPRO_NUM_PROCESSES/"
                         "REPRO_PROCESS_ID (repro.parallel.distributed; "
                         "no-op env -> this process's devices) and sweep "
                         "on an explicit global trial mesh — honored even "
                         "with a single device")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    if args.shard:
        # Grid membership was established at import (see top of module);
        # the explicit mesh pins the sweep to ALL global devices and is
        # honored even when only one is visible.
        from repro.parallel import sharding
        mesh = sharding.trial_mesh()
        main(quick=args.smoke, shard=mesh)
        if args.relaxed:
            main_relaxed(quick=args.smoke, shard=mesh)
    else:
        main(quick=args.smoke)
        if args.relaxed:
            main_relaxed(quick=args.smoke)
