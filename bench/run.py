"""Run one benchmark cell once, as one process, on the chip it finds.

    python3 bench/run.py --workload ffp_n11_lan.score --seed 7 \\
        --seconds 10 --trace 0

Steps: find the chip (exit non-zero without one: there is no CPU
fallback), turn on the program's persistent compile cache, build the
cell's inputs from the seed and warm up only the cell's own shapes (all
of that is ``setup_s``), measure for ``--seconds``, then check the
window's output against the plain reference.  ``--trace 1`` is a run of
its own: it profiles the window and reports the per-layer metrics in
place of the end-to-end ones.

Progress goes to standard error, ending with each compared number beside
its limit; the last line of standard output is the result as one JSON
object.  The cell, its configuration, its traffic and its per-layer
readers are all found by name from ``BENCHMARK.json``.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".trace")
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _compile_counter():
    """Backend compiles and persistent-cache reads: ``box[phase]`` counts
    them for the phase named in ``box["phase"]``."""
    import jax
    box = {"phase": "setup", "setup": [0, 0], "window": [0, 0],
           "check": [0, 0]}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box[box["phase"]][0] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            box[box["phase"]][1] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return box


def _profile_options():
    """Device ops and host spans; no Python call tracing, which would
    multiply the trace's size and slow the host it measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = None) -> dict:
    """Everything after the chip check; returns the result object."""
    import jax
    from harness import device, score, trace as trace_mod

    t_start = T_START if t_start is None else t_start
    compiles = _compile_counter()
    run = score.ScoreRun(cell, devices)
    log(f"{cell.name}: {run.m} systems, {run.trials} trials per pass, "
        f"chunk {run.chunk}, {run.ndev} device(s) in the trial mesh")
    score.warm_up(run, seed)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=_profile_options())
    setup_s = time.monotonic() - t_start
    compiles["phase"] = "window"
    batches = run.window(seed, seconds, log)
    compiles["phase"] = "check"
    if trace:
        jax.profiler.stop_trace()
    failed = sum(1 for b in batches if b.result is None)
    rate = run.trials_per_s(batches)
    peak = device.peak_bytes(devices)
    log(f"set-up {setup_s:.3f} s: {compiles['setup'][0]} compiles, "
        f"{compiles['setup'][1]} read from the cache")
    log(f"window: {len(batches)} batches ({failed} failed) in "
        f"{batches[-1].end - batches[0].start:.3f} s; "
        f"{compiles['window'][0]} compiles, {compiles['window'][1]} cache "
        f"reads inside it")
    attempted = len(batches)
    checks = run.check(seed, batches, log)
    del batches

    dev = dict(device.describe(devices), memory_peak_bytes=peak)
    if trace:
        tr = trace_mod.load(trace_mod.find_xplane(TRACE_DIR))
        metrics = {}
        for m in cell.per_layer:
            v = m["read"](tr, {"batches": tr.n_batches})
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=tr.mean_busy_s(), window_s=tr.window_s)
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.top_gaps()}
    else:
        values = {"system_trials_per_s": rate, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
    return _result(checks, metrics, dev, breakdown, sum(compiles["window"]),
                   attempted=attempted, failed=failed)


def _result(checks, metrics, dev, breakdown, window_compiles, *,
            attempted, failed) -> dict:
    from harness import compare
    out = {"correct": compare.is_correct(checks), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window_compiles"] = window_compiles
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from harness import cells, device
    try:
        cell = cells.load_cell(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        log(str(e))
        return 2
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        log(f"the program is not in this checkout: {e}")
        return 2
    try:
        devices = device.require(cell.chips)
    except device.NoChip as e:
        log(str(e))
        return 2
    import jax
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"{device.describe(devices)}; compile cache {cache_dir}")
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in res["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
