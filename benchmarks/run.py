"""Benchmark harness — one entry per paper table/figure, plus the framework's
own microbenches and the roofline table summary.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-kernels]
                                          [--json BENCH_quick.json]

Sections:
  fig2a / fig2b / fig2c   paper §6 reproduction (FP vs FFP, n=11)
  sweep                   beyond-paper quorum-space sweep (§5)
  qsys                    general quorum systems: cardinality vs grid vs
                          weighted in one masked compile (§6 closing remark)
  mc.*                    montecarlo engine end-to-end: whole spec table per
                          call, traced thresholds (DESIGN.md §2)
  stream.*                streaming engine: trials/sec at fixed memory,
                          10^7-trial acceptance row (DESIGN.md §7)
  stream.multihost.*      multi-host trial mesh: 2 procs x 4 forced host
                          devices vs 1 proc x 8 on the same global key —
                          bit-identity of the merged summary + throughput
                          (DESIGN.md §10; skipped where the platform has
                          no multi-process CPU collectives)
  frontier.*              mixed-family (grid + weighted + cardinality)
                          Pareto frontier on n=12 through the streamed
                          dominance scorer (DESIGN.md §8)
  planner.*               search-and-serve planner (DESIGN.md §11):
                          successive-halving search wall vs the exhaustive
                          sweep at the same final budget, cold vs warm
                          query latency, zero-compile warm queries, and a
                          service round trip
  regimes.*               Markov-modulated scenario regimes (DESIGN.md
                          §12): the committed trace_replay config streamed
                          end-to-end through Experiment.from_config —
                          throughput, per-regime occupancy split,
                          single-compile discipline
  relaxed.*               joint FFP + Relaxed Paxos frontier on n=11 under
                          both collision-recovery rules (DESIGN.md §13):
                          relaxed systems surviving the joint Pareto
                          reduction, one extra race compile for the
                          uncoordinated rule, rule-invariance checks
  kernel.*                per-kernel timing: jnp reference under jit (wall),
                          Pallas interpret-mode parity asserted in tests/
  roofline.*              aggregate of experiments/dryrun/*.json

Output: ``name,value`` CSV on stdout (timings in us where applicable).
``--json`` additionally writes the machine-readable benchmark record CI
diffs against ``BENCH_baseline.json`` (``benchmarks.check_regression``):
every metric row, per-section wall time and engine trace counts (compile
counts), plus environment metadata.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp


def _time_us(fn, *args, iters: int = 20) -> float:
    jax.block_until_ready(fn(*args))      # warm-up: compile once, any pytree
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


def kernel_benches(quick: bool):
    """Wall-time of the pure-jnp reference ops under jit (CPU).  The Pallas
    kernels themselves target TPU; on CPU they run in interpret mode (orders
    of magnitude slower by construction) so parity, not speed, is asserted —
    see tests/test_kernels.py."""
    rows = []
    key = jax.random.PRNGKey(0)

    from repro.kernels.flash_attention import ref as fa_ref
    B, H, S, D = (1, 4, 512, 64) if quick else (2, 8, 1024, 64)
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(key, (B, S, H, D), jnp.float32)
    v = jax.random.normal(key, (B, S, H, D), jnp.float32)
    fn = jax.jit(lambda q, k, v: fa_ref.attention(q, k, v, causal=True))
    rows.append((f"kernel.flash_attention.ref_us[{B}x{H}x{S}x{D}]",
                 _time_us(fn, q, k, v)))

    from repro.kernels.rmsnorm import ref as rn_ref
    x = jax.random.normal(key, (4096, 4096), jnp.float32)
    sc = jnp.ones((4096,))
    fn = jax.jit(lambda x, s: rn_ref.rmsnorm(x, s))
    rows.append(("kernel.rmsnorm.ref_us[4096x4096]", _time_us(fn, x, sc)))

    from repro.kernels.ssd_scan import ref as ssd_ref
    Bs, S2, nh, hd, ds = (1, 512, 4, 32, 32) if quick else (2, 1024, 8, 64, 64)
    xw = jax.random.normal(key, (Bs, S2, nh, hd), jnp.float32)
    da = -jnp.abs(jax.random.normal(key, (Bs, S2, nh), jnp.float32)) * 0.1
    Bm = jax.random.normal(key, (Bs, S2, ds), jnp.float32)
    Cm = jax.random.normal(key, (Bs, S2, ds), jnp.float32)
    fn = jax.jit(lambda *a: ssd_ref.ssd(*a)[0])
    rows.append((f"kernel.ssd_scan.ref_us[{Bs}x{S2}x{nh}x{hd}]",
                 _time_us(fn, xw, da, Bm, Cm)))

    from repro.kernels.quorum_tally import ref as qt_ref
    votes = jax.random.randint(key, (100_000, 11), 0, 2)
    fn = jax.jit(lambda v: qt_ref.tally_votes(v, 2))
    rows.append(("kernel.quorum_tally.ref_us[100000x11]", _time_us(fn, votes)))

    q = jnp.int32(7)
    fn = jax.jit(lambda v, q: qt_ref.tally_decide(v, 2, q))
    rows.append(("kernel.quorum_tally.decide_ref_us[100000x11]",
                 _time_us(fn, votes, q)))
    return rows


def montecarlo_benches(quick: bool):
    """End-to-end engine wall time: the whole n=11 minimal frontier (one
    mask-table lowering, "q"-specialized since the frontier is all
    cardinality) per call — the number the traced batching is meant to
    move.  Plus the declarative layer's overhead: one ``Experiment.run``
    against the same frontier, which should cost the same engine call."""
    import jax.numpy as jnp

    from benchmarks.quorum_sweep import enumerate_valid, minimal_frontier
    from repro.api import Experiment, Workload
    from repro.montecarlo import build_mask_table, engine

    frontier = minimal_frontier(enumerate_valid(11))
    table = build_mask_table(frontier)
    samples = 10_000 if quick else 100_000
    key = jax.random.PRNGKey(0)
    offs = jnp.array([0.0, 0.2], jnp.float32)
    rows = []

    fn = lambda k: engine.fast_path(k, table, n=11, samples=samples)
    rows.append((f"mc.engine.fast_path_us[{len(frontier)}specs.{samples}]",
                 _time_us(fn, key, iters=10)))
    fn = lambda k: engine.race(k, table, offs, n=11, k_proposers=2,
                               samples=samples)["latency_ms"]
    rows.append((f"mc.engine.race_us[{len(frontier)}specs.{samples}]",
                 _time_us(fn, key, iters=10)))

    exp = Experiment(systems=frontier, workload=Workload.race(k=2,
                                                              delta_ms=0.2),
                     samples=samples, compute_fault_tolerance=False)
    fn = lambda s: exp.run("montecarlo").raw["latency_ms"]
    rows.append((f"mc.api.experiment_us[{len(frontier)}specs.{samples}]",
                 _time_us(fn, 0, iters=10)))
    return rows


def streaming_benches(quick: bool):
    """Streaming engine throughput at fixed memory: trials/sec for the
    chunked fast-path and race drivers, and the 10^7-trial acceptance row
    through the Experiment front door (10^6 under --quick so the CI smoke
    job stays snappy).  Each timing is the second run — the first warms the
    one compile the scan reuses."""
    from repro.api import Experiment, Workload
    from repro.core.quorum import QuorumSpec
    from repro.montecarlo import build_mask_table, streaming

    rows = []
    key = jax.random.PRNGKey(0)
    table = build_mask_table([QuorumSpec.paper_headline(11),
                              QuorumSpec.fast_paxos(11)])
    t_fast = 1_000_000 if quick else 10_000_000
    t_race = 200_000 if quick else 2_000_000
    chunk = 131_072

    def timed(fn):
        jax.block_until_ready(jax.tree_util.tree_leaves(fn())[0])
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
        return out, time.perf_counter() - t0

    state, dt = timed(lambda: streaming.fast_path_stream(
        key, table, n=11, trials=t_fast, chunk=chunk))
    rows.append((f"stream.fast_path.trials_per_s[{t_fast}]", t_fast / dt))
    rows.append(("stream.fast_path.p999_ms", float(state.quantile(0.999)[0])))

    offs = jnp.array([0.0, 0.2], jnp.float32)
    state, dt = timed(lambda: streaming.race_stream(
        key, table, offs, n=11, k_proposers=2, trials=t_race, chunk=chunk))
    rows.append((f"stream.race.trials_per_s[{t_race}]", t_race / dt))
    rows.append(("stream.race.p99_ms", float(state.quantile(0.99)[0])))

    # the acceptance row: the declarative front door streams the same
    # trial count in one-chunk memory (fixed-size state asserted)
    exp = Experiment(systems=[QuorumSpec.paper_headline(11)],
                     workload=Workload.conflict_free(), trials=t_fast,
                     stream=streaming.StreamSettings(chunk=chunk),
                     compute_fault_tolerance=False)
    t0 = time.perf_counter()
    r = exp.run("montecarlo")
    jax.block_until_ready(r.stream.hist)
    assert int(r.stream.n_trials[0]) == t_fast
    rows.append((f"stream.experiment.wall_s[{t_fast}]",
                 time.perf_counter() - t0))
    rows.append(("stream.experiment.p50_ms", float(r.summary["p50_ms"][0])))
    rows.append(("stream.experiment.p999_ms",
                 float(r.summary["p999_ms"][0])))
    return rows


def multihost_benches(quick: bool):
    """Multi-host trial mesh acceptance as a benchmark row (DESIGN.md §10):
    launch the fixed stream workload on 2 processes x 4 forced host devices
    and on 1 process x 8, same global key, and record (a) bit-identity of
    the merged decide counts/histogram across layouts and (b) the
    distributed layout's throughput.  Skipped (no rows, a printed note)
    where the platform lacks multi-process CPU collectives —
    ``check_regression`` tolerates the missing ``stream.multihost``
    section."""
    import tempfile

    import numpy as np

    from repro.parallel import distributed

    trials = 50_011 if quick else 200_003      # odd: exercises remainders
    rows = []
    try:
        with tempfile.TemporaryDirectory() as td:
            multi = distributed.run_stream_layout(
                2, 4, os.path.join(td, "p2x4.npz"), trials=trials)
            single = distributed.run_stream_layout(
                1, 8, os.path.join(td, "p1x8.npz"), trials=trials)
    except NotImplementedError as e:      # the platform lacks gloo
        print(f"# stream.multihost skipped: {type(e).__name__}: "
              f"{str(e).splitlines()[0]}")
        return []
    bit = all(np.array_equal(multi[k], single[k])
              for k in ("n_trials", "n_fast", "n_recovery", "n_undecided",
                        "hist"))
    rows.append(("stream.multihost.bit_identical", 1.0 if bit else 0.0))
    rows.append((f"stream.multihost.trials_per_s[{trials}.2x4]",
                 trials / float(multi["wall_s"])))
    # per-system vectors (headline, fast_paxos); report the headline system
    rows.append(("stream.multihost.p999_ms", float(multi["p999_ms"][0])))
    rows.append(("stream.multihost.p9999_ms", float(multi["p9999_ms"][0])))
    assert bit, "2x4 vs 1x8 merged StreamSummary diverged (layout variance)"
    return rows


def mixed_family(n: int = 12):
    """Grid systems over the 3x4 factorization (plus narrower embeds),
    weighted voting, and the three cardinality landmarks as ONE mask batch
    — the general masked stream path, since a mixed batch carries no "q"
    specialization, and the only batch the fused megakernel serves."""
    from repro.core.quorum import QuorumSpec
    from repro.frontier import families

    return (
        [families.Member(f"card.{t}", s) for t, s in
         (("headline", QuorumSpec.paper_headline(n)),
          ("fast_paxos", QuorumSpec.fast_paxos(n)),
          ("majority", QuorumSpec.majority_fast(n)))]
        + families.grid_family(n) + families.weighted_family(n))


def frontier_benches(quick: bool):
    """Mixed-family Pareto frontier (DESIGN.md §8) on an n=12 cluster
    (``mixed_family``) scored by one ``fast_path_stream`` + one
    ``race_stream`` compile and reduced by the dominance kernel."""
    from repro.frontier import score_systems
    from repro.montecarlo import engine

    n = 12
    members = mixed_family(n)
    trials = 131_072 if quick else 2_000_000

    t0 = dict(engine.TRACE_COUNTS)
    s0 = time.perf_counter()
    fr = score_systems(members, n=n, trials=trials, chunk=8_192, shard=True,
                       seed=0)
    wall = time.perf_counter() - s0
    traces = (engine.TRACE_COUNTS["fast_path_stream"]
              - t0["fast_path_stream"],
              engine.TRACE_COUNTS["race_stream"] - t0["race_stream"])
    assert traces[0] <= 1 and traces[1] <= 1, (
        f"mixed-family frontier re-jitted: {traces}")

    rows = [("frontier.n_systems", len(fr.labels)),
            ("frontier.n_members", len(fr.frontier_indices)),
            ("frontier.engine_compiles", sum(traces)),
            (f"frontier.score_wall_s[{len(fr.labels)}sys.{trials}]", wall)]
    for i in fr.frontier_indices:
        row = fr.row(i)
        rows.append((f"frontier.[{fr.labels[i]}].fast_p50_ms",
                     row["fast_p50_ms"]))
        rows.append((f"frontier.[{fr.labels[i]}].race_p999_ms",
                     row["race_p999_ms"]))
    return rows


def planner_benches(quick: bool):
    """Search-and-serve planner (DESIGN.md §11): successive-halving over
    the full n=11 cardinality family vs the exhaustive sweep at the same
    final budget, then query latency cold vs warm.

    The cold query runs the whole search (every rung compiles fresh in a
    new ``EngineCache``); the warm query differs only in fault budget, so
    it must hit the search cache and add ZERO engine compiles — asserted
    here and regression-pinned via ``planner.warm_engine_compiles``.  The
    exhaustive pass scores all candidates at the final budget directly;
    the search's final-rung scores are bit-identical per system (common
    random numbers), so the frontier-set match is exact, not approximate.
    """
    import numpy as np

    from repro.frontier import families, score_systems
    from repro.planner import Planner, default_schedule

    n = 11
    final = 100_000 if quick else 1_000_000
    schedule = tuple((r.trials, r.slack)
                     for r in default_schedule(final, min_trials=10_000))
    planner = Planner()                     # fresh engine cache: clean cold
    query = dict(n=n, family="cardinality", trials=final, schedule=schedule,
                 chunk=16_384, shard=False, seed=0)

    t0 = time.perf_counter()
    cold = planner.plan(dict(query, faults={"classic": 1}))
    cold_wall = time.perf_counter() - t0
    warm_wall = float("inf")
    for _ in range(3):                      # best-of-3: stable on busy CI
        t0 = time.perf_counter()
        warm = planner.plan(dict(query, faults={"fast": 1, "phase1": 1}))
        warm_wall = min(warm_wall, time.perf_counter() - t0)
    assert warm.engine_compiles == 0 and not warm.cold, (
        f"warm same-geometry query recompiled: {warm.engine_compiles}")

    # the exhaustive sweep at the same final budget, for the wall-clock
    # and frontier-set comparison (scored after the search so no compile
    # is accidentally shared — the batch shapes differ anyway)
    members = families.cardinality_family(n)
    t0 = time.perf_counter()
    full = score_systems(members, n=n, trials=final, chunk=16_384,
                         shard=False, seed=0)
    exhaustive_wall = time.perf_counter() - t0
    sr = next(iter(planner._searches.values()))       # the cached search
    match = set(sr.frontier_labels) == set(full.frontier_labels)
    assert match, (f"search frontier {sorted(sr.frontier_labels)} != "
                   f"exhaustive {sorted(full.frontier_labels)}")

    rows = [
        ("planner.cold_query_wall_s", cold_wall),
        ("planner.warm_query_wall_s", warm_wall),
        ("planner.cold_engine_compiles", float(cold.engine_compiles)),
        ("planner.warm_engine_compiles", float(warm.engine_compiles)),
        ("planner.search_wall_s", float(sum(
            v for k, v in cold.search.items() if k.endswith(".wall_s")))),
        ("planner.exhaustive_wall_s", exhaustive_wall),
        ("planner.budget_fraction", float(cold.search["budget_fraction"])),
        ("planner.n_candidates", float(cold.search["n_candidates"])),
        ("planner.n_survivors", float(cold.search["n_survivors"])),
        ("planner.n_frontier", float(cold.search["n_frontier"])),
        ("planner.frontier_matches_exhaustive", 1.0 if match else 0.0),
    ]

    # service round-trip on the warm planner: JSON in, recommendation out
    from repro.planner import PlannerServer, query_server
    srv = PlannerServer(planner=planner, port=0, batch_window_s=0.01)
    srv.start()
    try:
        payload = {"op": "plan", **query, "faults": {"classic": 1},
                   "schedule": [list(r) for r in schedule]}
        t0 = time.perf_counter()
        reply = query_server(payload, port=srv.port)
        rt = time.perf_counter() - t0
        assert reply["ok"] and reply["engine_compiles"] == 0, reply
        rows.append(("planner.serve_warm_roundtrip_s", rt))
    finally:
        srv.shutdown()
    return rows


def regimes_benches(quick: bool):
    """Markov-modulated scenario regimes (DESIGN.md §12) from a committed
    scenario config: stream the ``trace_replay`` example (empirical
    trace-driven delay + 3-regime failure chain) end-to-end through
    ``Experiment.from_config`` and record throughput, the per-regime
    occupancy split, and the compile discipline — ONE fresh
    ``race_stream_regimes`` trace for the geometry, ZERO on a same-shape
    repeat (different seed re-enters the warm compile; trial counts and
    regime parameters are traced operands)."""
    import dataclasses

    from repro.api.experiment import Experiment
    from repro.montecarlo import engine

    cfg = os.path.join(os.path.dirname(__file__), "..",
                       "examples", "scenarios", "trace_replay.json")
    trials = 200_000 if quick else 1_000_000
    exp = Experiment.from_config(cfg)
    exp = dataclasses.replace(exp, trials=trials, stream=dataclasses.replace(
        exp.stream, shard=len(jax.devices()) > 1))

    t0 = dict(engine.TRACE_COUNTS)
    s0 = time.perf_counter()
    r = exp.run("montecarlo")
    jax.block_until_ready(r.stream.occupancy)
    wall = time.perf_counter() - s0
    compiles = (engine.TRACE_COUNTS["race_stream_regimes"]
                - t0["race_stream_regimes"])
    assert compiles == 1, (
        f"3-regime stream took {compiles} compiles (expected 1)")

    t1 = dict(engine.TRACE_COUNTS)
    r2 = dataclasses.replace(exp, seed=exp.seed + 1).run("montecarlo")
    jax.block_until_ready(r2.stream.occupancy)
    repeat = (engine.TRACE_COUNTS["race_stream_regimes"]
              - t1["race_stream_regimes"])
    assert repeat == 0, (
        f"same-geometry regime stream re-jitted ({repeat} traces)")

    rep = r.stream.report()
    rows = [("regimes.n_regimes", float(len(rep["names"]))),
            ("regimes.engine_compiles", float(compiles)),
            ("regimes.repeat_engine_compiles", float(repeat)),
            (f"regimes.trials_per_s[{trials}]", trials / wall),
            ("regimes.p999_ms", float(r.summary["p999_ms"][0]))]
    import numpy as np
    for i, name in enumerate(rep["names"]):
        rows.append((f"regimes.occupancy_frac.{name}",
                     float(rep["occupancy_frac"][i])))
        # per-system vector (scalar when M == 1); report the first system
        rows.append((f"regimes.[{name}].p50_ms",
                     float(np.ravel(rep["per_regime"][name]["p50_ms"])[0])))
    return rows


def roofline_summary(dryrun_dir: str = "experiments/dryrun"):
    rows = []
    files = sorted(glob.glob(os.path.join(dryrun_dir, "*.single.json")))
    fracs = []
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        if rec.get("status") != "ok":
            continue
        tag = f"{rec['arch']}.{rec['shape']}"
        r = rec.get("roofline", {})
        rows.append((f"roofline.{tag}.dominant={r.get('dominant', '?')}",
                     r.get("roofline_fraction", 0.0)))
        fracs.append(r.get("roofline_fraction", 0.0))
    if fracs:
        rows.append(("roofline.cells", len(fracs)))
        rows.append(("roofline.mean_fraction",
                     sum(fracs) / len(fracs)))
        rows.append(("roofline.min_fraction", min(fracs)))
        rows.append(("roofline.max_fraction", max(fracs)))
    return rows


def _sections(args):
    """(name, runner, prints_itself) triples in execution order."""
    def fig2a(q):
        from benchmarks import fig2a_latency
        return fig2a_latency.main(quick=q)

    def fig2b(q):
        from benchmarks import fig2b_conflict_latency
        return fig2b_conflict_latency.main(quick=q)

    def fig2c(q):
        from benchmarks import fig2c_conflict_prob
        return fig2c_conflict_prob.main(quick=q)

    def sweep(q):
        from benchmarks import quorum_sweep
        return quorum_sweep.main(quick=q)

    def relaxed(q):
        from benchmarks import quorum_sweep
        return quorum_sweep.main_relaxed(quick=q)

    def qsys(q):
        from benchmarks import quorum_systems
        return quorum_systems.main(quick=q)

    out = [("fig2a", fig2a, True), ("fig2b", fig2b, True),
           ("fig2c", fig2c, True), ("sweep", sweep, True),
           ("qsys", qsys, True), ("mc", montecarlo_benches, False),
           ("stream", streaming_benches, False),
           ("multihost", multihost_benches, False),
           ("frontier", frontier_benches, False),
           ("planner", planner_benches, False),
           ("regimes", regimes_benches, False),
           ("relaxed", relaxed, True)]
    if not args.skip_kernels:
        out.append(("kernels", kernel_benches, False))
    out.append(("roofline", lambda q: roofline_summary(), False))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig2a,fig2b,fig2c,sweep,"
                         "qsys,mc,stream,multihost,frontier,planner,"
                         "regimes,relaxed,kernels,roofline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable benchmark record "
                         "(metrics + per-section wall time + compile "
                         "counts) for benchmarks.check_regression")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro import compile_cache
    from repro.montecarlo import engine
    compile_cache.enable()

    metrics, sections = {}, {}
    t0 = time.time()
    for name, fn, prints_itself in _sections(args):
        if only is not None and name not in only:
            continue
        tc0 = dict(engine.TRACE_COUNTS)
        s0 = time.perf_counter()
        rows = fn(args.quick) or []
        wall = time.perf_counter() - s0
        if not prints_itself:
            for rname, val in rows:
                print(f"{rname},{val:.6g}")
        metrics.update({rname: float(val) for rname, val in rows})
        sections[name] = {
            "wall_s": wall,
            "engine_compiles": {k: v - tc0[k]
                                for k, v in engine.TRACE_COUNTS.items()
                                if v - tc0[k]},
        }
    total = time.time() - t0
    print(f"bench.total_wall_s,{total:.1f}")

    if args.json:
        record = {
            "meta": {
                "quick": bool(args.quick),
                "jax": jax.__version__,
                "platform": jax.default_backend(),
                "device_count": len(jax.devices()),
            },
            "sections": sections,
            "trace_counts": dict(engine.TRACE_COUNTS),
            "metrics": {**metrics, "bench.total_wall_s": total},
        }
        with open(args.json, "w") as fh:
            json.dump(record, fh, indent=1, sort_keys=True)
        print(f"bench.json_written,{args.json}")


if __name__ == "__main__":
    main()
