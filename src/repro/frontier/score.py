"""Streamed scorer: one mask batch, two stream passes, five frontier axes.

``score_systems`` evaluates an entire family batch through the streaming
engine (DESIGN.md §7) and extracts the per-system axes the quorum-space
tradeoff is about:

  fast_p50_ms    conflict-free fast-path median        (minimize)
  race_p999_ms   p99.9 commit latency under a K-way    (minimize)
                 race — the tail axis only streamed
                 trial counts make meaningful, and the
                 axis that finally prices q2c (the
                 recovery quorum dominates the tail)
  p_recovery     P(coordinated recovery | race)        (minimize)
  ft_fast        steady-state fast-path crash budget   (maximize)
  ft_phase1      crashes survivable for recovery       (maximize)
  ft_classic     classic phase-2 crash budget          (maximize —
                 without it, systems whose races never
                 recover tie on every axis across all
                 q2c choices and the frontier degenerates)

Everything latency-shaped comes from exactly two ``StreamSummary`` states —
one ``fast_path_stream`` pass and one ``race_stream`` pass over the whole
batch — so every system sees identical sampled delays (common random
numbers) and one compile covers the entire family per engine path.  Fault
tolerance is arithmetic for cardinality specs and brute force over the
masks otherwise (embedding-invariant: zero-weight acceptors never help a
crash set kill a quorum).

Latency axes carry the sketch's relative ``precision`` as their dominance
epsilon and the rate axis a 3-sigma binomial epsilon at the streamed trial
count, so the Pareto mask never splits ties the measurement cannot
actually resolve (``pareto.quantize``).
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quorum import QuorumMasks, QuorumSpec
from repro.montecarlo import engine, streaming

from .families import Member
from .pareto import Axis, FrontierResult, pareto_mask

DEFAULT_TRIALS = 1_000_000
DEFAULT_DELTA_MS = 0.2
# The chunk is smaller than streaming's default: the race path
# materializes (M, chunk, n) gathers per system inside the scan, and
# frontier batches run to hundreds of systems.
DEFAULT_STREAM = streaming.StreamSettings(chunk=8_192)

AXIS_NAMES = ("fast_p50_ms", "race_p999_ms", "p_recovery", "ft_fast",
              "ft_phase1", "ft_classic")


def default_axes(precision: float = streaming.DEFAULT_PRECISION,
                 trials: int = DEFAULT_TRIALS) -> Tuple[Axis, ...]:
    """The standard six-axis frontier, epsilons matched to what the
    measurement can resolve: sketch precision on latencies (relative,
    log-grid), 3-sigma binomial noise on the recovery rate, exact on the
    integral fault-tolerance axes."""
    rate_eps = 3.0 * math.sqrt(0.25 / max(trials, 1))
    return (Axis("fast_p50_ms", maximize=False, eps=precision,
                 relative=True),
            Axis("race_p999_ms", maximize=False, eps=precision,
                 relative=True),
            Axis("p_recovery", maximize=False, eps=rate_eps),
            Axis("ft_fast", maximize=True),
            Axis("ft_phase1", maximize=True),
            Axis("ft_classic", maximize=True))


def _as_masks(systems: Sequence, n: Optional[int]) -> Tuple[List[QuorumMasks],
                                                            List, int]:
    """Normalize Members / systems / raw masks to one shared cluster size.
    Returns (masks, native systems, n)."""
    native, masks = [], []
    for s in systems:
        if isinstance(s, Member):
            native.append(s.system)
            masks.append(s.masks())
        elif isinstance(s, QuorumMasks):
            native.append(s)
            masks.append(s)
        else:
            native.append(s)
            masks.append(s.to_masks())
    target = max(m.n for m in masks) if n is None else n
    masks = [m if m.n == target else m.embed(target) for m in masks]
    return masks, native, target


def _fault_tolerance(system, masks: QuorumMasks) -> Dict[str, int]:
    """Crash budgets: arithmetic for cardinality specs (any n), brute
    force over the mask encoding otherwise."""
    if isinstance(system, QuorumSpec):
        return system.fault_tolerance()
    return masks.fault_tolerance()


class _Lowered(NamedTuple):
    """A batch lowered once for the engine: its masks, the systems they
    came from, the cluster size, the labels and the mask table."""
    masks: List[QuorumMasks]
    native: List
    n: int
    labels: Tuple[str, ...]
    table: Dict


def _lower(systems: Sequence, n: Optional[int]) -> _Lowered:
    """Encode the masks and build the table, under ``repro.score.table``."""
    span = jax.profiler.TraceAnnotation
    with span("repro.score.table"):
        with span("repro.score.masks"):
            masks, native, n = _as_masks(systems, n)
        labels = tuple(m.label or f"system{i}" for i, m in enumerate(masks))
        table = engine.build_mask_table(masks)
    return _Lowered(masks, native, n, labels, table)


def score_systems(systems: Sequence, *,
                  trials: int = DEFAULT_TRIALS,
                  n: Optional[int] = None,
                  k_proposers: int = 2,
                  delta_ms: float = DEFAULT_DELTA_MS,
                  delay=None,
                  chunk: int = DEFAULT_STREAM.chunk,
                  precision: float = DEFAULT_STREAM.precision,
                  shard: bool = DEFAULT_STREAM.shard,
                  use_kernel: bool = DEFAULT_STREAM.use_kernel,
                  k_max=DEFAULT_STREAM.k_max,
                  seed: int = 0,
                  regimes=None,
                  recovery: str = "coordinated",
                  axes: Optional[Sequence[Axis]] = None) -> FrontierResult:
    """Score a family batch and return its Pareto frontier.

    ``systems`` is any mix of ``families.Member``, quorum systems, or raw
    ``QuorumMasks``; smaller systems embed into the largest cluster size
    present (or an explicit ``n``).  The whole batch streams through
    ``fast_path_stream`` and ``race_stream`` at ``trials`` trials each —
    one compile per engine path, fixed memory, trial axis sharded over
    local devices when ``shard`` — and the five default axes (or a custom
    ``axes`` tuple matching ``AXIS_NAMES``) feed ``pareto.pareto_mask``.
    ``chunk`` / ``precision`` / ``shard`` / ``use_kernel`` / ``k_max`` are
    the fields of ``streaming.StreamSettings`` (defaults:
    ``DEFAULT_STREAM``).

    ``k_max`` selects the sort-free streamed lowering (DESIGN.md §9):
    ``"auto"`` (default) derives the per-phase top-k selection depths from
    the mask table, ``None`` keeps the full-sort reference path, and an
    explicit int / 3-tuple pins the depths.  Integer outputs (decide bits,
    counts, histograms — hence every frontier axis) are bit-identical
    across all settings; only wall clock changes.

    ``regimes`` (a ``MarkovRegimes`` or its config dict) modulates both
    stream passes through Markov failure epochs; the scored axes then
    read the regime-merged totals, so the frontier prices the *mixture*
    the workload declares rather than a single i.i.d. environment.

    ``recovery`` selects the collision-recovery rule priced by the race
    pass (``engine.RECOVERY_MODES``); ``p_recovery`` is rule-invariant (the
    entry condition is), but the tail axis re-prices q2c vs q2f.

    Host spans on the profiler's clock (``jax.profiler.TraceAnnotation``)
    split each call: ``repro.score`` around it all; ``repro.score.table``
    (holding ``repro.score.masks``, the host-side mask encoding) for the
    mask table; ``repro.stream.fast_path`` and ``repro.stream.race`` for the
    two stream dispatches; ``repro.score.readback`` for the reads that wait
    on the device; ``repro.score.frontier`` for fault tolerance and the
    Pareto mask.
    """
    stream = streaming.StreamSettings(chunk=chunk, precision=precision,
                                      shard=shard, use_kernel=use_kernel,
                                      k_max=k_max)
    with jax.profiler.TraceAnnotation("repro.score", systems=len(systems),
                                      trials=trials, seed=seed):
        return _score(_lower(systems, n), trials=trials,
                      k_proposers=k_proposers, delta_ms=delta_ms,
                      delay=delay, stream=stream, seed=seed,
                      regimes=regimes, recovery=recovery, axes=axes)


def _score(low: _Lowered, *, trials: int, k_proposers: int,
           delta_ms: float, delay, stream: streaming.StreamSettings,
           seed: int, regimes, recovery: str,
           axes: Optional[Sequence[Axis]]) -> FrontierResult:
    """``score_systems`` on a lowered batch: the two stream passes, the
    readback and the frontier."""
    span = jax.profiler.TraceAnnotation
    axes = (tuple(axes) if axes is not None
            else default_axes(stream.precision, trials))
    key = jax.random.PRNGKey(seed)
    k_fast, k_race = jax.random.split(key)
    offsets = delta_ms * jnp.arange(k_proposers, dtype=jnp.float32)
    kw = dict(stream.to_flat(), regimes=regimes)
    use_kernel = kw.pop("use_kernel")
    fast = streaming.fast_path_stream(k_fast, low.table, delay, n=low.n,
                                      trials=trials, **kw)
    race = streaming.race_stream(k_race, low.table, offsets, delay, n=low.n,
                                 k_proposers=k_proposers, trials=trials,
                                 use_kernel=use_kernel, recovery=recovery,
                                 **kw)

    with span("repro.score.readback"):
        fast_p50 = np.asarray(fast.quantile(0.5), np.float64)
        race_p999 = np.asarray(race.quantile(0.999), np.float64)
        p_rec = (np.asarray(race.n_recovery, np.float64)
                 / np.maximum(np.asarray(race.n_trials, np.float64), 1.0))
    with span("repro.score.frontier"):
        ft = [_fault_tolerance(s, m) for s, m in zip(low.native, low.masks)]
        values = np.stack([
            fast_p50,
            race_p999,
            p_rec,
            np.array([f["steady_state_fast"] for f in ft], np.float64),
            np.array([f["phase1"] for f in ft], np.float64),
            np.array([f["phase2_classic"] for f in ft], np.float64),
        ], axis=1)
        return FrontierResult(labels=low.labels, axes=axes, values=values,
                              mask=pareto_mask(values, axes),
                              streams={"fast": fast, "race": race})
