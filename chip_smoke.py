"""Smoke run of the FFP evaluator's main path on a TPU, in one process.

    python chip_smoke.py             # one chip: sweep, fused kernel, planner,
                                     # means check
    python chip_smoke.py --chips 4   # four chips: the sharded race_stream
                                     # against its per-device merge, only

One chip runs, at the sizes users run them:

1. the streamed n=11 quorum-space sweep (``benchmarks.quorum_sweep.run``):
   all 271 FFP-valid systems, 10^7 trials per engine path, with the
   sweep's own asserts (one compile per path on the sort-free lowering,
   agreement with the per-spec reference, minimal set within the frontier);
2. the masked n=12 grid + weighted + cardinality batch through the fused
   Pallas megakernel and through the jnp path on the same key: the kernel
   must be compiled for the TPU (not interpreted), decide counts must be
   equal, p50 and p99.9 within one sketch bucket;
3. the planner service: a cold and a warm n=11 query over its socket;
4. the means check: streamed card-path ``mean_ms`` against the same
   latencies materialized and summed on the host without a matmul.

``--chips 4`` runs only a 10^7-trial ``race_stream`` of the pinned
multi-host workload on the 4-chip trial mesh, and the same four
per-device streams (same global-index keys and trial counts) one after
another on one chip, merged on the host: counts and histogram must be
bit-identical.

Data comes from fixed seeds.  Every earlier line is a log of this one smoke
run, not a benchmark number.  The last line is the JSON result
``{"ok": true, "device": {...}}``; any failure exits non-zero without it.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Phase:
    """Context manager that logs a phase's wall time and engine compiles."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro.montecarlo import engine
        self.tc0 = dict(engine.TRACE_COUNTS)
        self.t0 = time.perf_counter()
        log(f"{self.name}: start")
        return self

    def __exit__(self, *exc):
        from repro.montecarlo import engine
        wall = time.perf_counter() - self.t0
        compiles = {k: v - self.tc0[k] for k, v in engine.TRACE_COUNTS.items()
                    if v != self.tc0[k]}
        status = "failed" if exc[0] is not None else "passed"
        log(f"{self.name}: {status}, wall {wall:.3f} s (compiles included), "
            f"engine traces {compiles}")
        return False


# ---------------------------------------------------------------------------
# Phases (importable; sizes are arguments so tests can run them small).
# ---------------------------------------------------------------------------

def phase_sweep(quick: bool = False) -> dict:
    """The n=11 sweep exactly as ``benchmarks.quorum_sweep`` runs it,
    unsharded (one chip)."""
    from benchmarks import quorum_sweep
    rows = dict(quorum_sweep.run(quick=quick, shard=False))
    log(f"sweep: {int(rows['sweep.n_valid_configs'])} systems, "
        f"{int(rows['sweep.trials'])} trials per path, "
        f"{int(rows['sweep.n_frontier_systems'])} on the frontier, "
        f"{int(rows['sweep.engine_compiles'])} engine compiles, "
        f"{rows['sweep.trials_per_sec']:.6g} trials/s over both paths "
        f"(compiles included)")
    return rows


def _kernel_is_native(table, n: int, k_proposers: int, chunk: int,
                      precision: float) -> bool:
    """Whether the megakernel the fused stream path calls lowers to a TPU
    custom call at this chunk shape (False when Pallas interprets it)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.quorum_tally import ops as qt_ops
    from repro.montecarlo import engine, streaming

    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    shapes = [sds((chunk, n), jnp.int32), sds((chunk, k_proposers, n), f32),
              sds((chunk, n), f32), sds((chunk, n), f32)]
    for ph in ("p1", "p2c", "p2f"):
        shapes += [sds(table[ph + "_w"].shape, f32),
                   sds(table[ph + "_t"].shape, f32)]
    shapes.append(sds((chunk,), jnp.bool_))
    fn = lambda *a: qt_ops.stream_tally_decide_hist(
        *a, n_values=k_proposers, k_sat=engine.saturation_depths(table),
        precision=precision, bins=streaming.sketch_bins(precision),
        undecided_ms=float(engine.UNDECIDED_MS))
    return "tpu_custom_call" in jax.jit(fn).lower(*shapes).as_text()


def phase_kernel(trials: int = 2_000_000, chunk: int = 8_192) -> dict:
    """Fused megakernel vs the jnp path on the masked n=12 mixed batch,
    same key.  Returns the comparison; raises on a mismatch."""
    import numpy as np

    from benchmarks.run import mixed_family
    from repro.frontier import score_systems
    from repro.frontier.score import _as_masks
    from repro.montecarlo import engine, streaming

    n = 12
    members = mixed_family(n)
    runs = {}
    for use_kernel in (True, False):
        t0 = dict(engine.TRACE_COUNTS)
        fr = score_systems(members, n=n, trials=trials, chunk=chunk,
                           shard=False, seed=0, use_kernel=use_kernel)
        fused = engine.TRACE_COUNTS["race_stream_fused"] \
            - t0["race_stream_fused"]
        assert fused == (1 if use_kernel else 0), (
            f"use_kernel={use_kernel} traced the fused path {fused} times")
        runs[use_kernel] = fr.streams["race"]
    kern, ref = runs[True], runs[False]

    masks, _, _ = _as_masks(members, n)
    table = engine.build_mask_table(masks)
    native = _kernel_is_native(table, n, 2, chunk, kern.precision)

    for k in ("n_trials", "n_fast", "n_recovery", "n_undecided"):
        a, b = np.asarray(getattr(kern, k)), np.asarray(getattr(ref, k))
        assert np.array_equal(a, b), f"{k}: kernel {a} vs jnp {b}"
    log_g = math.log(streaming.sketch_gamma(kern.precision))
    worst = 0
    for q in (0.5, 0.999):
        a = np.asarray(kern.quantile(q), np.float64)
        b = np.asarray(ref.quantile(q), np.float64)
        both = np.isfinite(a) & np.isfinite(b)
        assert np.array_equal(np.isnan(a), np.isnan(b)), (q, a, b)
        steps = np.rint(np.abs(np.log(a[both] / b[both])) / log_g)
        worst = max(worst, int(steps.max(initial=0)))
        assert (steps <= 1).all(), (
            f"q={q}: kernel {a} vs jnp {b} differ by more than one bucket")
    hist_diff = int((np.asarray(kern.hist) != np.asarray(ref.hist)).sum())
    out = {"n_systems": len(members), "trials": trials, "native": native,
           "hist_entries_differing": hist_diff,
           "quantile_bucket_steps_max": worst}
    log(f"kernel: {len(members)} systems x {trials} trials; megakernel "
        f"{'compiled for the TPU' if native else 'interpreted'}; decide "
        f"counts equal; p50/p99.9 within {worst} bucket(s); "
        f"{hist_diff} histogram entries differ")
    return out


def phase_planner(trials=None) -> dict:
    """Cold and warm n=11 cardinality queries through an in-process
    ``PlannerServer`` and ``query_server``.  ``trials`` None keeps the
    planner's default final budget."""
    from repro.planner import PlannerServer, query_server

    base = {"op": "plan", "n": 11, "family": "cardinality", "seed": 0}
    if trials is not None:
        base["trials"] = trials
    srv = PlannerServer(port=0, batch_window_s=0.01)
    srv.start()
    try:
        t0 = time.perf_counter()
        cold = query_server(dict(base, faults={"classic": 1}), port=srv.port)
        t1 = time.perf_counter()
        warm = query_server(dict(base, faults={"fast": 1, "phase1": 1}),
                            port=srv.port)
        t2 = time.perf_counter()
    finally:
        srv.shutdown()
    assert cold.get("ok") and warm.get("ok"), (cold, warm)
    assert warm["engine_compiles"] == 0, warm["engine_compiles"]
    assert not warm["cold"], "warm query ran a new search"
    assert cold["frontier_labels"] == warm["frontier_labels"], (
        cold["frontier_labels"], warm["frontier_labels"])
    log(f"planner: cold query {t1 - t0:.3f} s ({cold['engine_compiles']} "
        f"engine compiles) -> {cold['recommended']}; warm query "
        f"{t2 - t1:.3f} s (0 compiles) -> {warm['recommended']}; "
        f"frontier of {len(cold['frontier_labels'])}")
    return {"cold": cold, "warm": warm}


def means_check(chunks: int = 4, chunk: int = 8_192,
                rtol: float = 1e-5) -> float:
    """Streamed card-path ``mean_ms`` of all 271 n=11 systems against the
    same latencies, materialized chunk by chunk through ``engine.race``
    and summed on the host in float64 (no matmul).  Returns the worst
    relative error; raises past ``rtol``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.frontier import cardinality_family
    from repro.montecarlo import build_mask_table, engine, streaming

    table = build_mask_table([m.system for m in cardinality_family(11)])
    assert "q" in table
    key = jax.random.PRNGKey(7)
    offsets = jnp.array([0.0, 0.2], jnp.float32)
    st = streaming.race_stream(key, table, offsets, n=11, k_proposers=2,
                               trials=chunks * chunk, chunk=chunk,
                               shard=False)
    m = table["q"].shape[0]
    lat_sum = np.zeros(m, np.float64)
    n_dec = np.zeros(m, np.int64)
    for c in range(chunks):
        out = engine.race(jax.random.fold_in(key, c), table, offsets, n=11,
                          k_proposers=2, samples=chunk)
        dec = np.asarray(out["reached_fast"] | out["recovery"])
        lat = np.asarray(out["latency_ms"], np.float64)
        lat_sum += np.where(dec, lat, 0.0).sum(axis=1)
        n_dec += dec.sum(axis=1)
    assert np.array_equal(n_dec, np.asarray(st.n_decided)), \
        "streamed decide counts differ from the materialized chunks"
    has = n_dec > 0
    want = lat_sum[has] / n_dec[has]
    got = np.asarray(st.mean_ms, np.float64)[has]
    rel = float(np.max(np.abs(got - want) / want))
    log(f"means: {m} systems x {chunks * chunk} trials, worst relative "
        f"error of streamed mean_ms {rel:.3e} (limit {rtol:g})")
    assert rel <= rtol, f"streamed mean_ms off by {rel:.3e} relative"
    return rel


def four_chip(trials: int = 10_000_000, chunk: int = 65_536,
              seed: int = 0) -> dict:
    """The pinned multi-host workload (``distributed._stream_worker``:
    paper-headline + Fast Paxos, n=11, Δ=0.2 ms) as one ``race_stream`` on
    the trial mesh over the first four devices, against the four
    per-device streams run one after another on device 0 and merged on the
    host.  Counts, histogram and max must be bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.quorum import QuorumSpec
    from repro.montecarlo import build_mask_table, streaming
    from repro.parallel import sharding as psharding

    devices = jax.devices()[:4]
    assert len(devices) == 4, f"need 4 devices, found {len(jax.devices())}"
    table = build_mask_table([QuorumSpec.paper_headline(11),
                              QuorumSpec.fast_paxos(11)])
    offsets = jnp.array([0.0, 0.2], jnp.float32)
    key = jax.random.PRNGKey(seed)
    kw = dict(n=11, k_proposers=2, chunk=chunk, precision=0.01)

    t0 = time.perf_counter()
    mesh = psharding.trial_mesh(devices)
    sharded = streaming.race_stream(key, table, offsets, trials=trials,
                                    shard=mesh, **kw)
    jax.block_until_ready(sharded.hist)
    wall_sharded = time.perf_counter() - t0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    shards = sorted(str(s.device) for s in sharded.hist.addressable_shards)

    per_dev = []
    t0 = time.perf_counter()
    dom = jax.random.fold_in(key, jnp.int32(streaming.DEVICE_FOLD_DOMAIN))
    for d in range(4):
        t_d = trials // 4 + (1 if d < trials % 4 else 0)
        s = streaming.race_stream(jax.random.fold_in(dom, d), table,
                                  offsets, trials=t_d, shard=False, **kw)
        per_dev.append(jax.tree_util.tree_map(np.asarray, s))
    wall_ref = time.perf_counter() - t0

    merged = {k: sum(getattr(s, k) for s in per_dev)
              for k in ("n_trials", "n_fast", "n_recovery", "n_undecided",
                        "hist")}
    merged["max_ms"] = np.maximum.reduce([s.max_ms for s in per_dev])
    for k, v in merged.items():
        got = np.asarray(getattr(sharded, k))
        assert np.array_equal(got, v), f"{k}: sharded {got} vs merged {v}"
    assert (merged["n_trials"] == trials).all()
    per_dev_trials = [int(s.n_trials[0]) for s in per_dev]
    log(f"4 chips: {trials} trials on mesh {[str(d) for d in devices]}; "
        f"result shards on {shards}; per-device peak bytes {peaks}")
    log(f"4 chips: per-device streams of {per_dev_trials} trials merged on "
        f"the host are bit-identical in counts, histogram and max; sharded "
        f"wall {wall_sharded:.3f} s, sequential reference {wall_ref:.3f} s "
        f"(compiles included)")
    if all(p is not None for p in peaks):
        assert all(p > 0 for p in peaks), f"an idle device: {peaks}"
    return {"trials": trials, "per_device_trials": per_dev_trials,
            "peak_bytes": peaks, "bit_identical": True}


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------

def _check_checkout() -> None:
    """Refuse to run outside a checkout of this repository."""
    pkg = os.path.join(ROOT, "src", "repro", "montecarlo", "streaming.py")
    if not os.path.isfile(pkg):
        sys.exit(f"chip_smoke: {pkg} not found; run from a checkout of the "
                 f"repository")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def _cache_counters() -> dict:
    import jax
    counts = {"hits": 0, "misses": 0}
    events = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in events:
            counts[events[event]] += 1
    jax.monitoring.register_event_listener(listen)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded trial-mesh comparison")
    args = ap.parse_args(argv)
    _check_checkout()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{platform!r} ({len(devices)} device(s)); no CPU fallback")
    kind = devices[0].device_kind
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    cache = _cache_counters()
    log(f"device_kind={kind!r} count={len(devices)} jax={jax.__version__}; "
        f"one smoke run, not a benchmark; compile cache {cache_dir}")

    if args.chips == 4:
        with Phase("four-chip trial mesh"):
            four_chip()
    else:
        with Phase("phase 1 sweep"):
            phase_sweep()
        with Phase("phase 2 fused kernel"):
            res = phase_kernel()
            assert res["native"], "the megakernel ran interpreted on the TPU"
        with Phase("phase 3 planner"):
            phase_planner()
        with Phase("means check"):
            means_check()
    log(f"compile cache: {cache['hits']} hits, {cache['misses']} misses")
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
