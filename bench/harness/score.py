"""Driver of the ``score_batches`` traffic: set-up, window, check.

The entry the window drives is the program's ``frontier.score_systems``,
called as a user calls it: the whole system space, the cell's trials per
pass, and a fresh seed per batch.  Each call is one batch; it returns
after its fast pass, its race pass and the host frontier work.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from . import compare, reference, systems, traffic as traffic_mod


@dataclass
class Batch:
    index: int
    seed: int
    start: float
    end: float
    result: object = None          # FrontierResult, None when it raised


class ScoreRun:
    """One cell's program inputs, built once in set-up."""

    def __init__(self, cell, devices):
        from repro.montecarlo.latency import delay_from_config

        self.cfg = cell.config
        self.traffic = traffic_mod.validate(cell.traffic)
        self.n = int(self.cfg["n"])
        self.defs = systems.enumerate_systems(self.cfg)
        self.members = systems.to_program(self.defs, self.n)
        self.race = self.cfg["race"]
        self.delay = delay_from_config(dict(self.cfg["delay"]), self.n)
        self.trials = int(self.traffic["trials"])
        self.chunk = int(self.traffic["chunk"])
        # a cell on more than one chip shards the trial axis over them
        self.shard = cell.chips > 1
        self.ndev = len(devices) if self.shard else 1
        self.m = len(self.defs)
        self.precision = float(self.cfg["sketch"]["precision"])

    def score(self, seed: int):
        from repro.frontier import score_systems
        return score_systems(
            self.members, trials=self.trials, n=self.n,
            k_proposers=int(self.race["k_proposers"]),
            delta_ms=float(self.race["delta_ms"]), delay=self.delay,
            chunk=self.chunk, precision=self.precision,
            shard=self.shard, use_kernel=False,
            seed=seed, recovery=self.race["recovery"])

    # -- the window --------------------------------------------------------
    def window(self, run_seed: int, seconds: float,
               log: Callable[[str], None]) -> List[Batch]:
        import jax
        batches: List[Batch] = []
        t0 = time.monotonic()
        b = 1
        while b == 1 or time.monotonic() - t0 < seconds:
            seed = traffic_mod.batch_seed(run_seed, b)
            start = time.monotonic()
            res = None
            with jax.profiler.TraceAnnotation("bench.batch"):
                try:
                    with jax.profiler.TraceAnnotation("bench.score_systems"):
                        res = self.score(seed)
                except Exception as e:             # counted as failed
                    log(f"batch {b} failed: {type(e).__name__}: {e}")
            batches.append(Batch(b, seed, start, time.monotonic(), res))
            b += 1
        return batches

    def trials_per_s(self, batches: List[Batch]) -> float:
        done = [x for x in batches if x.result is not None]
        span = batches[-1].end - batches[0].start
        return len(done) * self.m * self.trials / span

    # -- the check ---------------------------------------------------------
    @staticmethod
    def extract(fr) -> Dict[str, np.ndarray]:
        out = {"values": np.asarray(fr.values, np.float64),
               "mask": np.asarray(fr.mask, bool),
               "race_p50": np.asarray(fr.streams["race"].quantile(0.5),
                                      np.float64)}
        for side in ("race", "fast"):
            s = fr.streams[side]
            for f in ("n_trials", "n_fast", "n_recovery", "n_undecided",
                      "hist"):
                out[f"{side}.{f}"] = np.asarray(getattr(s, f), np.int64)
        return out

    def check(self, run_seed: int, batches: List[Batch],
              log: Callable[[str], None]) -> Dict:
        """Compare one window batch, drawn from the run seed, with the
        reference; every batch's trial counts are checked too.  Frees the
        window's program state before the reference runs."""
        done = [x for x in batches if x.result is not None]
        trial_gap = 0
        for x in done:
            for side in ("race", "fast"):
                got = np.asarray(x.result.streams[side].n_trials, np.int64)
                trial_gap = max(trial_gap,
                                int(np.abs(got - self.trials).max()))
        pick = traffic_mod.checked_batch(run_seed, len(batches))
        chosen = next(x for x in batches if x.index == pick)
        if chosen.result is None:
            return {"count_gap": (float("inf"), 0.0)}
        prog = self.extract(chosen.result)
        for x in batches:
            x.result = None
        gc.collect()
        t0 = time.monotonic()
        checks = self.numbers(prog, self.reference(chosen.seed), trial_gap)
        log(f"reference of batch {pick} (seed {chosen.seed}): "
            f"{time.monotonic() - t0:.3f} s")
        return checks

    def reference(self, seed: int, dtype=None, rank_slack=None) -> Dict:
        """The plain reference's counts and order statistics of the batch
        drawn from ``seed``, computed at ``dtype`` (float32 by default)."""
        import jax.numpy as jnp
        g = self.cfg["guarantees"]
        return reference.score(
            seed, systems.reference_rows(self.defs, self.n), n=self.n,
            k=int(self.race["k_proposers"]),
            delta_ms=float(self.race["delta_ms"]), delay=self.cfg["delay"],
            trials=self.trials, chunk=self.chunk, ndev=self.ndev,
            recovery=self.race["recovery"], sketch=self.cfg["sketch"],
            dtype=jnp.float32 if dtype is None else dtype,
            rank_slack=(int(g["quantile_rank_slack"]) if rank_slack is None
                        else rank_slack))

    def numbers(self, prog: Dict, ref: Dict, trial_gap: int = 0) -> Dict:
        ft = systems.reference_fault_tolerance(self.defs, self.n)
        return compare.compare(prog, ref, ft, self.cfg, self.trials,
                               window_trial_gap=trial_gap)

    def as_program(self, ref: Dict) -> Dict[str, np.ndarray]:
        """A reference result shaped like ``extract``'s output (each
        quantile at its rank), to stand in the program's place: the
        control."""
        ft = systems.reference_fault_tolerance(self.defs, self.n)
        p_rec = (ref["race_recovery"].astype(np.float64)
                 / np.maximum(ref["trials"].astype(np.float64), 1.0))
        mid = lambda x: x[:, x.shape[1] // 2]
        values = np.concatenate([mid(ref["fast_p50"])[:, None],
                                 mid(ref["race_p999"])[:, None],
                                 p_rec[:, None], ft], axis=1)
        out = {"values": values, "race_p50": mid(ref["race_p50"]),
               "mask": reference.frontier_mask(
                   values, self.precision, self.trials)}
        for p_key, r_key in compare.RACE_COUNTS:
            out[f"race.{p_key}"] = ref[r_key]
        for p_key, r_key in compare.FAST_COUNTS:
            out[f"fast.{p_key}"] = ref[r_key]
        out["race.hist"], out["fast.hist"] = ref["race_hist"], ref["fast_hist"]
        return out


def warm_up(run: ScoreRun, run_seed: int) -> Optional[object]:
    """Batch 0: the window's own shapes, once, so nothing compiles in it."""
    return run.score(traffic_mod.batch_seed(run_seed, 0))
