"""Named scenario builders: a scenario bundles race geometry (how many
proposers, at what offsets) with a delay model, and knows how to run itself
over a quorum-system *mask* table (``engine.build_mask_table`` — the single
lowering for cardinality, grid, weighted and explicit systems) in one
engine call.

Builders cover the paper's §6 workloads plus the deployments the relaxation
is aimed at:

  conflict_free      Fig. 2a — one proposer, pure fast-path order statistics
  k_way_race         Fig. 2b/2c generalized — K proposers staggered by Δ
  mixed_workload     fraction p of commands race, the rest are clean
  wan                geo-distributed acceptors (multi-region delay matrix)
  lossy_acceptors    i.i.d. message loss on every hop
  grid_wan           §6 closing remark: a 3xC grid system whose rows ARE the
                     WAN regions (returns scenario + masks)
  weighted_acceptors Gifford-style weighted voting with optional crashes
                     (returns scenario + masks)

The last two pair a workload with the quorum system it is built around and
support per-acceptor fault injection (``CrashedDelay``), so quorum structure
and failure placement can be studied together — e.g. crashing a whole grid
row versus the same number of scattered acceptors.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.quorum import (ExplicitQuorumSystem, QuorumMasks,
                               WeightedQuorumSystem)

from . import engine, streaming
from .latency import (CrashedDelay, LossyDelay, ShiftedLognormalDelay,
                      WanDelay, default_delay)
from .streaming import StreamSettings


@dataclass(frozen=True)
class RunSpec:
    """Execution knobs of a scenario run, carried BY the scenario.

    ``Scenario.run`` / ``summary`` / ``stream`` take only (key, table);
    every execution knob lives here, stated once:
    ``scenario.with_spec(trials=10**7, faults=(0, 3)).stream(key, table)``.

    ``samples`` sizes materializing runs (``run``/``summary``), ``trials``
    streamed ones; ``stream`` holds the engine's ``StreamSettings`` (the
    materializing runs read only its ``use_kernel``).  ``faults`` crashes
    those acceptor ids
    for the run (``CrashedDelay``); ``regimes`` (a
    ``regimes.MarkovRegimes`` or its config dict) Markov-modulates a
    streamed run through failure epochs (DESIGN.md §12); ``recovery``
    selects the collision-recovery rule (``engine.RECOVERY_MODES``).
    """

    samples: int = 20000
    trials: int = 1_000_000
    stream: StreamSettings = StreamSettings()
    faults: Tuple[int, ...] = ()
    regimes: Optional[object] = None
    recovery: str = "coordinated"

    def merged(self, **overrides) -> "RunSpec":
        """This spec with every non-None override applied."""
        kw = {k: v for k, v in overrides.items() if v is not None}
        return replace(self, **kw) if kw else self


@dataclass(frozen=True)
class Scenario:
    """A runnable workload: K proposers at ``offsets_ms`` under ``delay``.

    ``conflict_frac`` < 1 mixes in conflict-free commands: the reported
    per-spec latency distribution is the blend, as in Fig. 2b.  ``spec``
    carries the execution knobs (``RunSpec``).
    """

    name: str
    n: int
    k_proposers: int
    offsets_ms: jax.Array            # (K,)
    delay: object
    conflict_frac: float = 1.0
    spec: RunSpec = RunSpec()

    def with_spec(self, spec: Optional[RunSpec] = None, **kw) -> "Scenario":
        """Carry these execution knobs: ``with_spec(trials=10**7)``
        overrides fields of the current spec; ``with_spec(RunSpec(...))``
        replaces it outright (then applies any overrides)."""
        base = self.spec if spec is None else spec
        if kw:
            base = replace(base, **kw)
        return replace(self, spec=base)

    def with_faults(self, crashed: Sequence[int]) -> "Scenario":
        """Inject per-acceptor crashes: every hop touching a crashed
        acceptor is lost (``CrashedDelay``)."""
        if not len(tuple(crashed)):
            return self
        return replace(self, delay=CrashedDelay(
            self.delay, _crash_mask(self.n, crashed)))

    def run(self, key: jax.Array, table) -> Dict[str, jax.Array]:
        """Evaluate every quorum system in ``table`` (a ``build_mask_table``
        dict — cardinality, grid, weighted and explicit systems all lower to
        it) over ``spec.samples`` instances.

        Returns (M, S)-shaped ``latency_ms`` plus race outcome flags (for the
        racing fraction) — one engine compile per (shape, scenario type).
        Execution knobs come from ``self.spec`` only (``with_spec``)."""
        return self._run(key, table, self.spec)

    def _run(self, key: jax.Array, table,
             spec: RunSpec) -> Dict[str, jax.Array]:
        scen = self.with_faults(spec.faults)
        samples = spec.samples
        m = table["p1_w"].shape[0]
        if self.k_proposers == 1 or self.conflict_frac == 0.0:
            lat = engine.fast_path(key, table, scen.delay, n=self.n,
                                   samples=samples)
            undecided = lat >= engine.UNDECIDED_MS   # fast path never arrived
            return {"latency_ms": lat, "reached_fast": ~undecided,
                    "recovery": jnp.zeros((m, samples), bool),
                    "undecided": undecided,
                    "fast_winner": jnp.where(undecided, -1, 0).astype(
                        jnp.int32)}

        k_race, k_free = jax.random.split(key)
        n_conf = max(1, int(round(samples * self.conflict_frac)))
        out = engine.race(k_race, table, self.offsets_ms, scen.delay,
                          n=self.n, k_proposers=self.k_proposers,
                          samples=n_conf, use_kernel=spec.stream.use_kernel,
                          recovery=spec.recovery)
        n_free = samples - n_conf
        if n_free > 0:
            scen_free = Scenario(self.name, self.n, 1, self.offsets_ms[:1],
                                 scen.delay)
            free = scen_free._run(k_free, table,
                                  replace(spec, samples=n_free, faults=()))
            out = {k: jnp.concatenate([free[k], out[k]], axis=-1)
                   for k in out}
        return out

    def summary(self, key: jax.Array, table) -> Dict[str, jax.Array]:
        """Per-system latency quantiles + outcome rates, each entry (M,).

        Quantiles cover *decided* instances only; instances that never
        gathered enough votes (message loss) are reported separately via
        ``undecided_rate`` instead of polluting the distribution with the
        LOST_MS sentinel (``engine.summarize``)."""
        return engine.summarize(self._run(key, table, self.spec))

    def stream(self, key: jax.Array, table):
        """Streamed evaluation: ``spec.trials`` instances reduced
        chunk-by-chunk into a fixed-size ``streaming.StreamSummary`` (device
        memory is one chunk regardless of the trial count; the trial axis
        shards over local devices when ``spec.stream.shard``).  A mixed workload
        streams its racing and conflict-free fractions separately and
        *merges* the two summaries — sketch merge is exact, so the blend
        matches a single mixed stream.

        ``spec.stream.k_max`` selects the sort-free lowering (DESIGN.md §9):
        "auto" derives per-phase top-k selection depths from the table,
        ``None`` keeps the full-sort reference path; integer outputs are
        identical.  ``spec.regimes`` Markov-modulates the stream through
        failure epochs and returns a ``RegimeStreamSummary`` instead
        (DESIGN.md §12).  Execution knobs come from ``self.spec`` only
        (``with_spec``).
        """
        return self._stream(key, table, self.spec)

    def _stream(self, key: jax.Array, table, spec: RunSpec):
        scen = self.with_faults(spec.faults)
        trials = spec.trials
        kw = dict(spec.stream.to_flat(), regimes=spec.regimes)
        use_kernel = kw.pop("use_kernel")
        if self.k_proposers == 1 or self.conflict_frac == 0.0:
            return streaming.fast_path_stream(key, table, scen.delay,
                                              n=self.n, trials=trials, **kw)
        k_race, k_free = jax.random.split(key)
        n_conf = max(1, int(round(trials * self.conflict_frac)))
        state = streaming.race_stream(k_race, table, self.offsets_ms,
                                      scen.delay, n=self.n,
                                      k_proposers=self.k_proposers,
                                      trials=n_conf, use_kernel=use_kernel,
                                      recovery=spec.recovery, **kw)
        if trials - n_conf > 0:
            free = streaming.fast_path_stream(k_free, table, scen.delay,
                                              n=self.n,
                                              trials=trials - n_conf, **kw)
            state = state.merge(free)
        return state


# ---------------------------------------------------------------------------
# Builders.
# ---------------------------------------------------------------------------

def conflict_free(n: int = 11, delay=None) -> Scenario:
    """Fig. 2a: a steady conflict-free stream; latency is the q2f-th order
    statistic of client->acceptor->learner paths."""
    return Scenario("conflict_free", n, 1, jnp.zeros((1,)),
                    delay if delay is not None else default_delay())


def k_way_race(k: int, delta_ms: float = 0.5, n: int = 11,
               delay=None) -> Scenario:
    """K proposals race for one instance; proposer i submits at i * Δ.
    k=2, Δ swept reproduces Fig. 2c; larger k models hotter keys."""
    if k < 2:
        raise ValueError("a race needs at least 2 proposers")
    offs = delta_ms * jnp.arange(k, dtype=jnp.float32)
    return Scenario(f"{k}_way_race", n, k, offs,
                    delay if delay is not None else default_delay())


def mixed_workload(conflict_frac: float = 0.10, delta_ms: float = 0.5,
                   k: int = 2, n: int = 11, delay=None) -> Scenario:
    """Fig. 2b: ``conflict_frac`` of commands race (K-way, Δ apart), the
    rest commit conflict-free."""
    base = k_way_race(k, delta_ms, n, delay)
    return replace(base, name="mixed_workload", conflict_frac=conflict_frac)


def wan(n: int = 11, k: int = 2, inter_region_ms: float = 30.0,
        n_regions: int = 3, delta_ms: float = 0.5) -> Scenario:
    """Geo-distributed deployment: acceptors round-robin across
    ``n_regions`` regions ``inter_region_ms`` apart (one-way), proposers in
    distinct regions.  Here quorum choice interacts with *which* acceptors
    are near, not just how many — the regime the relaxation targets."""
    delay = WanDelay.symmetric(inter_region_ms, n, k, n_regions)
    offs = delta_ms * jnp.arange(k, dtype=jnp.float32)
    return Scenario("wan", n, k, offs, delay)


def lossy_acceptors(loss_prob: float = 0.01, k: int = 2,
                    delta_ms: float = 0.5, n: int = 11,
                    inner=None) -> Scenario:
    """Every hop independently drops with ``loss_prob``; lost proposals mean
    missing votes, surfacing as higher recovery and ``undecided`` rates."""
    delay = LossyDelay(inner if inner is not None else default_delay(),
                       loss_prob)
    offs = delta_ms * jnp.arange(k, dtype=jnp.float32)
    return Scenario("lossy_acceptors", n, k, offs, delay)


# ---------------------------------------------------------------------------
# General-quorum-system workloads (the §6 closing remark): each builder
# returns (scenario, masks) — the workload and the quorum system it is
# built around — ready for ``engine.build_mask_table`` + ``Scenario.run``.
# ---------------------------------------------------------------------------

def _crash_mask(n: int, crashed: Sequence[int]) -> jnp.ndarray:
    m = jnp.zeros((n,), bool)
    if len(tuple(crashed)):
        m = m.at[jnp.array(sorted(set(crashed)), jnp.int32)].set(True)
    return m


def grid_wan(cols: int = 3, k: int = 2, inter_region_ms: float = 30.0,
             delta_ms: float = 0.5,
             crashed: Sequence[int] = ()) -> Tuple[Scenario, QuorumMasks]:
    """A 3xC grid quorum system deployed so each grid *row* is a WAN region.

    Acceptor r*cols + c sits in region r; phase-2 classic quorums (columns)
    span all three regions, fast quorums (row pairs) need two full regions —
    quorum choice is now about *which* acceptors, the regime the paper's
    relaxation targets.  ``crashed`` injects acceptor failures (e.g. a whole
    row = a region outage vs the same count scattered across regions).
    """
    system = ExplicitQuorumSystem.grid(cols)
    n, rows = system.n, 3
    ow = inter_region_ms * (1.0 - jnp.eye(rows))
    delay = WanDelay(oneway_ms=ow,
                     acceptor_region=(jnp.arange(n, dtype=jnp.int32) // cols),
                     proposer_region=jnp.arange(k, dtype=jnp.int32) % rows,
                     learner_region=jnp.int32(0))
    if len(tuple(crashed)):
        delay = CrashedDelay(delay, _crash_mask(n, crashed))
    offs = delta_ms * jnp.arange(k, dtype=jnp.float32)
    return Scenario("grid_wan", n, k, offs, delay), system.to_masks()


def weighted_acceptors(weights: Sequence[int] = (2, 2, 2, 1, 1, 1, 1, 1, 1, 1, 1),
                       thresholds: Optional[Tuple[int, int, int]] = None,
                       k: int = 2, delta_ms: float = 0.5,
                       crashed: Sequence[int] = ()) -> Tuple[Scenario, QuorumMasks]:
    """Gifford-style weighted voting: heavyweight acceptors shrink quorum
    *cardinality* on the fast path while the FFP weight inequalities keep
    safety.  Default thresholds mirror the paper-headline shape in weight
    space — t1 = ceil(3W/4), then the minimal valid phase-2 thresholds
    (t1 + t2c > W, t1 + 2*t2f > 2W) — so all three phases tolerate
    crashes; ``crashed`` injects failures (a heavy node costs more than a
    light one).
    """
    n, total = len(weights), sum(weights)
    if thresholds is None:
        t1 = math.ceil(3 * total / 4)
        t2c = total - t1 + 1                    # Eq.13 analogue, weights
        t2f = (2 * total - t1) // 2 + 1         # Eq.14 analogue, weights
        thresholds = (t1, t2c, t2f)
    system = WeightedQuorumSystem(tuple(weights), *thresholds).validate()
    delay = default_delay()
    if len(tuple(crashed)):
        delay = CrashedDelay(delay, _crash_mask(n, crashed))
    offs = delta_ms * jnp.arange(k, dtype=jnp.float32)
    return Scenario("weighted_acceptors", n, k, offs, delay), system.to_masks()
