"""The comparison that decides ``correct`` for a scored batch.

Three numbers, each with its limit:

``count_gap``         largest absolute difference, over systems, passes
                      and outcomes (trials, fast, recovery, undecided),
                      between the program's counts and the reference's on
                      the same draws; also every window batch's trial
                      count against the traffic's.  Exact: limit 0.
``quantile_rel_err``  largest relative distance of the program's fast p50,
                      race p50 and race p99.9 from the exact order
                      statistic at the quantile's rank (or a neighbour
                      within the stated rank slack).  The limit is the
                      configuration's stated sketch precision plus its
                      stated float allowance.
``sketch_moved``      largest share, over systems and passes, of the
                      decided trials that the program's sketch holds in
                      another bucket than the reference puts them in
                      (half the L1 distance of the two histograms over the
                      decided count).  Set from readings: see
                      ``SKETCH_MOVED_LIMIT``.
``frontier_gap``      systems whose crash budgets, recovery rate or
                      frontier membership differ from the reference's
                      (membership recomputed by the reference from the
                      program's latency scores).  Exact: limit 0.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from . import reference

RACE_COUNTS = (("n_trials", "trials"), ("n_fast", "race_fast"),
               ("n_recovery", "race_recovery"),
               ("n_undecided", "race_undecided"))
FAST_COUNTS = (("n_trials", "trials"), ("n_fast", "fast_decided"),
               ("n_undecided", "fast_undecided"))


# Sound runs read 0 (the same float32 latencies, bucketed by the same
# mapping); the bfloat16 control moves a sizeable share of every
# histogram.  The limit leaves room for a lowering whose log rounds
# differently at bucket edges.  Readings: PERF.md, "How correct is decided".
SKETCH_MOVED_LIMIT = 1e-3


def limits(config: Dict) -> Dict[str, float]:
    g = config["guarantees"]
    return {"count_gap": 0.0,
            "quantile_rel_err": float(g["quantile_relative_error"])
            + float(g["quantile_float_allowance"]),
            "sketch_moved": SKETCH_MOVED_LIMIT,
            "frontier_gap": 0.0}


def _moved(hist: np.ndarray, ref_hist: np.ndarray) -> float:
    hist = np.asarray(hist, np.int64)
    if hist.shape != ref_hist.shape:
        return float("inf")
    n = np.maximum(ref_hist.sum(axis=1), 1)
    return float((np.abs(hist - ref_hist).sum(axis=1) / (2.0 * n)).max())


def _rel_err(v: np.ndarray, around: np.ndarray) -> np.ndarray:
    """(M,) program values against (M, S) exact order statistics: the
    smallest relative distance to any of them (inf where one side has a
    value and the other has none)."""
    v = np.asarray(v, np.float64)[:, None]
    with np.errstate(invalid="ignore", divide="ignore"):
        err = np.abs(v - around) / np.abs(around)
    both_nan = np.isnan(v[:, 0]) & np.isnan(around).all(axis=1)
    err = np.where(np.isnan(err), np.inf, err).min(axis=1)
    return np.where(both_nan, 0.0, err)


def compare(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            ft_ref: np.ndarray, config: Dict, trials: int,
            window_trial_gap: int = 0) -> Dict[str, Tuple[float, float]]:
    """``prog`` holds ``race.<field>`` / ``fast.<field>`` counts and
    histograms, ``values`` (M, 6), ``mask`` (M,) and ``race_p50`` (M,)."""
    gaps = [int(window_trial_gap)]
    for side, pairs in (("race", RACE_COUNTS), ("fast", FAST_COUNTS)):
        for p_key, r_key in pairs:
            d = np.abs(np.asarray(prog[f"{side}.{p_key}"], np.int64)
                       - ref[r_key])
            gaps.append(int(d.max()))
    values = np.asarray(prog["values"], np.float64)
    q_err = max(float(_rel_err(values[:, 0], ref["fast_p50"]).max()),
                float(_rel_err(values[:, 1], ref["race_p999"]).max()),
                float(_rel_err(prog["race_p50"], ref["race_p50"]).max()))
    p_rec = (ref["race_recovery"].astype(np.float64)
             / np.maximum(ref["trials"].astype(np.float64), 1.0))
    scored = np.concatenate([values[:, :2], p_rec[:, None], ft_ref], axis=1)
    mask = reference.frontier_mask(
        scored, float(config["sketch"]["precision"]), trials)
    wrong = ((values[:, 3:] != ft_ref).any(axis=1)
             | (values[:, 2] != p_rec)
             | (np.asarray(prog["mask"], bool) != mask))
    moved = max(_moved(prog["race.hist"], ref["race_hist"]),
                _moved(prog["fast.hist"], ref["fast_hist"]))
    lim = limits(config)
    return {"count_gap": (float(max(gaps)), lim["count_gap"]),
            "quantile_rel_err": (q_err, lim["quantile_rel_err"]),
            "sketch_moved": (moved, lim["sketch_moved"]),
            "frontier_gap": (float(wrong.sum()), lim["frontier_gap"])}


def is_correct(checks: Dict[str, Tuple[float, float]]) -> bool:
    """Every number at or under its limit (NaN fails)."""
    return all(v <= lim for v, lim in checks.values())
