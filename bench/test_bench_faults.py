"""A whole run at a size the CPU holds, without the look for a chip, with
the timed path broken underneath: ``correct`` has to come out false for
each fault a one-chip scoring cell can have, and true without one."""
import importlib.util
import os

import jax
import pytest

from harness import cells, faults

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_run(fault=None, seed=2 ** 32 + 5):
    cell = cells.load_cell("ffp_n11_lan.score", ROOT)
    cell.traffic = dict(cell.traffic, trials=6_000, chunk=2_048)
    run = bench_run()
    if fault is None:
        return run.run_cell(cell, seed, 0.2, False, jax.devices()[:1])
    with faults.FAULTS[fault]():
        return run.run_cell(cell, seed, 0.2, False, jax.devices()[:1])


def test_sound_run_is_correct_and_reports_its_metrics():
    res = tiny_run()
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"system_trials_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"count_gap", "quantile_rel_err",
                                  "sketch_moved", "frontier_gap"}
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_mask", "answer_quantile",
                                   "answer_count"])
def test_fault_makes_the_run_incorrect(fault):
    res = tiny_run(fault)
    assert res["correct"] is False, res["checks"]
