"""The plain reference against the program at a size the CPU holds:
counts equal, quantiles within the stated precision, frontier equal; and
the control (the reference in bfloat16 in the program's place) comes out
not correct."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cells, compare, reference, score

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cell(config, trials=6_000, chunk=2_048):
    """A one-chip scoring cell of a configuration file, at a size the CPU
    holds (read from the files, so it needs no ``BENCHMARK.json`` entry)."""
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "score.json")) as f:
        traffic = dict(json.load(f), trials=trials, chunk=chunk)
    return cells.Cell(name=config + ".score", chips=1, config=cfg,
                      traffic=traffic, end_to_end=[], per_layer=[])


@pytest.fixture(scope="module", params=["ffp_n11_lan", "joint_n11_wan"])
def run_and_ref(request):
    run = score.ScoreRun(tiny_cell(request.param), jax.devices()[:1])
    seed = 2 ** 31 + 99
    return run, seed, run.reference(seed)


def test_program_agrees_with_the_reference(run_and_ref):
    run, seed, ref = run_and_ref
    nums = run.numbers(run.extract(run.score(seed)), ref)
    assert nums["count_gap"][0] == 0
    assert nums["sketch_moved"][0] == 0
    assert nums["frontier_gap"][0] == 0
    assert nums["quantile_rel_err"][0] <= nums["quantile_rel_err"][1]
    assert compare.is_correct(nums)


def test_bfloat16_control_is_not_correct(run_and_ref):
    run, seed, ref = run_and_ref
    control = run.as_program(run.reference(seed, dtype=jnp.bfloat16,
                                           rank_slack=0))
    nums = run.numbers(control, ref)
    assert not compare.is_correct(nums)
    # under the WAN placement votes follow the regions, so the counts can
    # hold; the sketch never does
    assert nums["sketch_moved"][0] > 10 * nums["sketch_moved"][1]


def test_reference_is_deterministic(run_and_ref):
    run, seed, ref = run_and_ref
    again = run.reference(seed)
    for k in ("race_fast", "race_recovery", "fast_decided"):
        np.testing.assert_array_equal(ref[k], again[k])
    np.testing.assert_array_equal(ref["race_p999"], again["race_p999"])


def test_reference_counts_add_up(run_and_ref):
    run, _, ref = run_and_ref
    assert (ref["trials"] == run.trials).all()
    total = ref["race_fast"] + ref["race_recovery"] + ref["race_undecided"]
    assert (total == run.trials).all()
    assert (ref["race_undecided"] == 0).all()      # no message is lost


def test_frontier_mask_dominance():
    v = np.array([[1.0, 2.0, 0.1, 1, 1, 1],
                  [1.0, 2.0, 0.1, 1, 1, 0],        # dominated by row 0
                  [2.0, 1.0, 0.1, 1, 1, 1],        # a trade-off: kept
                  [1.001, 2.0, 0.1, 1, 1, 1]])     # a tie within eps: kept
    assert reference.frontier_mask(v, 0.01, 10 ** 6).tolist() == [
        True, False, True, True]


def test_rank_neighbours_are_clipped():
    idx = reference._ranks(0.999, np.array([1000, 1, 0]), 1)
    assert idx.tolist() == [[997, 998, 999], [0, 0, 0], [0, 0, 0]]


def test_reference_refuses_the_materialising_path():
    with open(os.path.join(ROOT, "bench", "configs",
                           "ffp_n11_lan.json")) as f:
        cfg = json.load(f)
    with pytest.raises(ValueError, match="trials must exceed chunk"):
        reference.draws(1, n=11, k=2, delta_ms=0.2, delay=cfg["delay"],
                        trials=100, chunk=128, ndev=1,
                        recovery="coordinated", dt=jnp.float32)
