"""Readings that set the limits of a cell's check, on the chip.

    python3 bench/control.py --workload ffp_n11_lan.score \\
        --seeds 11,12,13 [--faults]

For each run seed, the cell's first window batch is scored by the program
and compared with the float32 reference, giving the sound readings (the
lower ones).  The same reference computed in bfloat16 then stands in the
program's place, giving the control's readings (the upper ones); it has
to come out not correct.  ``--faults`` also plants each fault of
``harness/faults.py`` that the cell can have and reads the numbers.
Results are one JSON object per line; the last line sums them up.  The
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seeds, devices, faults: bool, emit) -> dict:
    import jax.numpy as jnp

    from harness import compare, score, traffic
    from harness import faults as fault_mod

    run = score.ScoreRun(cell, devices)
    names = [f for f in fault_mod.FAULTS if faults
             and (f != "no_exchange" or run.ndev > 1)]
    table = {}
    for seed in seeds:
        bseed = traffic.batch_seed(seed, 1)
        ref = run.reference(bseed)
        rows = {"sound": run.numbers(run.extract(run.score(bseed)), ref),
                "control": run.numbers(run.as_program(
                    run.reference(bseed, dtype=jnp.bfloat16, rank_slack=0)),
                    ref)}
        for f in names:
            with fault_mod.FAULTS[f]():
                rows[f] = run.numbers(run.extract(run.score(bseed)), ref)
        for kind, nums in rows.items():
            row = {"seed": seed, "kind": kind,
                   "correct": compare.is_correct(nums),
                   **{k: v for k, (v, _) in nums.items()}}
            emit(row)
            table.setdefault(kind, []).append(row)
    summary = {"limits": compare.limits(cell.config)}
    for kind, rows in table.items():
        pick = max if kind == "sound" else min
        summary[kind] = {k: pick(r[k] for r in rows)
                         for k in summary["limits"]}
        summary[kind]["all_correct"] = all(r["correct"] for r in rows)
        summary[kind]["none_correct"] = not any(r["correct"] for r in rows)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from harness import cells, device
    cell = cells.load_cell(args.workload, ROOT)
    try:
        devices = device.require(cell.chips)
    except device.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    emit = lambda row: print(json.dumps(row), flush=True)
    summary = readings(cell, seeds, devices, args.faults, emit)
    print(json.dumps({"workload": cell.name, **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
