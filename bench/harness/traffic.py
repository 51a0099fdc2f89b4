"""One generator for every traffic mix: the mix is a data file of
parameters, and the run seed fixes everything drawn from it.

``score_batches``: a closed loop of one caller sending back-to-back
``score_systems`` batches of ``trials`` trials per pass, streamed in
chunks of ``chunk``.  Batch 0 is the set-up's warm-up batch; the
window's batches are 1, 2, ...  Each batch's seed is folded from the run
seed and its index, so the same run seed always gives the same batches,
and a seed of any size (run seeds may exceed 32 bits) gives seeds the
program's 32-bit ``PRNGKey`` takes.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

KINDS = ("score_batches",)
SCORE_KEYS = {"kind", "trials", "chunk", "why"}


def validate(traffic: Dict) -> Dict:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}; known: {KINDS}")
    extra = set(traffic) - SCORE_KEYS
    if extra:
        raise ValueError(f"unknown traffic keys {sorted(extra)}")
    if int(traffic["trials"]) <= int(traffic["chunk"]):
        raise ValueError("trials must exceed chunk (the streamed path)")
    return traffic


def batch_seed(run_seed: int, batch: int) -> int:
    """31-bit seed of window batch ``batch`` (0 = warm-up)."""
    if run_seed < 0 or batch < 0:
        raise ValueError("seeds and batch indices are non-negative")
    state = np.random.SeedSequence([run_seed, batch]).generate_state(
        1, np.uint32)
    return int(state[0] & 0x7FFFFFFF)


def checked_batch(run_seed: int, n_batches: int) -> int:
    """Which of the window's batches (1..n_batches) the check compares,
    drawn from the run seed."""
    if n_batches < 1:
        raise ValueError("no batch completed in the window")
    rng = np.random.default_rng([run_seed, 0x636B])
    return 1 + int(rng.integers(n_batches))
