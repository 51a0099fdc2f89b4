"""Markov-modulated failure regimes over the streamed trial axis.

A streamed run today draws every trial from ONE static environment.  Real
deployments sweep through *epochs*: a diurnal baseline, a gray-failure
degradation, an asymmetric partition, a burst of rack-correlated crashes.
This module makes that sweep a first-class, single-compile part of the
streaming engine (DESIGN.md §12):

  regimes        ``MarkovRegimes``: R named regimes, each a FULL delay +
                 fault environment (any ``latency``/``traces`` pytree,
                 ``CrashedDelay``/``LossyDelay`` wrappers included), plus
                 an (R, R) transition matrix and an epoch length in
                 trials.
  chain          the regime of trial ``t`` is ``z[t // epoch_trials]``
                 where ``z`` is a Markov chain stepped once per epoch from
                 its own fold-in key domain (``REGIME_FOLD_DOMAIN`` —
                 disjoint from chunk and device domains).  The epoch
                 mapping lives in TRIAL index space, not chunk space, so
                 regime occupancy is invariant under the ``chunk`` size
                 (property-tested) and the chain prefix is the same for
                 any scan length.
  scan           ``streaming._stream`` samples each chunk under ALL R
                 environments and selects per-trial by regime id
                 (``_RegimeMixedDelay``), then scatters the chunk's
                 outcomes into PER-REGIME ``StreamSummary`` slices — one
                 ``lax.scan``, one compile per table shape, trials and
                 every environment parameter traced.
  merge          per-regime slices ride the existing integer-exact merges:
                 ``axis_merge`` across devices inside ``shard_map``, and
                 ``RegimeStreamSummary.total()`` across regimes — decide
                 counts and histograms are exact sums, so the marginal
                 summary equals a single mixed stream bit-for-bit.

Degenerate single-regime chains keep the i.i.d. contract: with R == 1 the
mixed-delay wrapper passes the chunk key through unfolded, so draws,
decide bits, counts and histograms are bit-identical to the plain
``race_stream``/``fast_path_stream`` on the same key (acceptance-tested).

Declarative configs (the scenario-suite JSON shape, satellite of the
``Workload`` schema)::

    {"epoch_trials": 8192,
     "regimes": [
       {"name": "baseline"},                           # inherit base delay
       {"name": "degraded",
        "delay": {"kind": "pareto", "scale_ms": 0.8},
        "loss_prob": 0.02},
       {"name": "partitioned", "crashed": [0, 1, 2]}],
     "transition": [[0.98, 0.01, 0.01],
                    [0.10, 0.88, 0.02],
                    [0.20, 0.00, 0.80]]}

``MarkovRegimes.from_config`` builds the concrete pytree (resolving
delay kinds through the ``latency`` registry); ``to_config`` inverts it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel import sharding as psharding

from .latency import (CrashedDelay, LossyDelay, PROPOSAL, delay_from_config,
                      delay_to_config)

# First-level fold-in tag for the regime chain's key stream.  Chunk c of a
# stream draws from fold_in(key, c); device d re-keys through
# fold_in(fold_in(key, DEVICE_FOLD_DOMAIN=0x7FFFFFFF), d); the regime
# chain steps from fold_in(fold_in(key, REGIME_FOLD_DOMAIN), epoch).  The
# tag sits next to the device domain at the top of int32 space — disjoint
# from any realistic chunk index — and differs from DEVICE_FOLD_DOMAIN,
# so all three key families are collision-free.
REGIME_FOLD_DOMAIN = 0x7FFFFFFE

# Default epoch length: regimes persist for thousands of trials (the
# correlated-failure point), while 10^6-trial runs still see hundreds of
# transitions.
DEFAULT_EPOCH_TRIALS = 8192

_ROW_SUM_TOL = 1e-6


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class MarkovRegimes:
    """R named regime environments + an (R, R) Markov transition matrix.

    ``delays[r]`` is the full delay+fault environment of regime r (any
    registered delay pytree); ``None`` entries inherit the scenario's base
    delay at bind time (``bound``).  ``transition[i, j]`` is
    P(next = j | current = i); rows must sum to 1 (``validate``).  The
    chain starts in regime ``start`` and steps once every
    ``epoch_trials`` trials.

    The transition matrix and every environment parameter are traced
    leaves; only the regime count, names, epoch length and start index are
    static — re-weighting the chain or refitting an environment re-enters
    the same compile.
    """

    names: Tuple[str, ...]
    delays: Tuple[object, ...]
    transition: jax.Array           # (R, R) float32
    epoch_trials: int = DEFAULT_EPOCH_TRIALS
    start: int = 0

    def tree_flatten(self):
        return ((self.delays, self.transition),
                (self.names, self.epoch_trials, self.start))

    @classmethod
    def tree_unflatten(cls, aux, children):
        delays, transition = children
        names, epoch_trials, start = aux
        return cls(names=names, delays=tuple(delays), transition=transition,
                   epoch_trials=epoch_trials, start=start)

    @property
    def n_regimes(self) -> int:
        return len(self.names)

    # -- validation --------------------------------------------------------
    def validate(self) -> "MarkovRegimes":
        """Host-side invariants (concrete transition matrix only): square
        (R, R) matrix matching the regime count, non-negative entries,
        every row summing to 1, valid start index, positive epoch."""
        r = self.n_regimes
        if r < 1:
            raise ValueError("MarkovRegimes needs at least one regime")
        if len(self.delays) != r:
            raise ValueError(f"{r} regime names but {len(self.delays)} "
                             f"delay environments")
        if len(set(self.names)) != r:
            raise ValueError(f"regime names must be unique, "
                             f"got {self.names}")
        t = np.asarray(self.transition, np.float64)
        if t.shape != (r, r):
            raise ValueError(f"transition matrix must be ({r}, {r}) for "
                             f"{r} regimes, got {t.shape}")
        if np.any(t < 0) or not np.all(np.isfinite(t)):
            raise ValueError("transition probabilities must be finite and "
                             ">= 0")
        rows = t.sum(axis=1)
        bad = np.nonzero(np.abs(rows - 1.0) > _ROW_SUM_TOL)[0]
        if bad.size:
            raise ValueError(
                f"transition rows must sum to 1: row(s) "
                f"{[self.names[i] for i in bad]} sum to "
                f"{rows[bad].tolist()}")
        if not 0 <= self.start < r:
            raise ValueError(f"start regime {self.start} out of range "
                             f"[0, {r})")
        if self.epoch_trials < 1:
            raise ValueError(f"epoch_trials must be >= 1, "
                             f"got {self.epoch_trials}")
        return self

    # -- binding -----------------------------------------------------------
    def bound(self, base_delay) -> "MarkovRegimes":
        """Substitute the scenario's base delay into inheriting slots:
        ``None`` becomes the base model itself, deferred loss/crash
        wrappers wrap it (idempotent once every slot is concrete)."""
        def _bind(d):
            if d is None:
                return base_delay
            if isinstance(d, (_DeferredCrash, _DeferredLoss)):
                return d.bind(base_delay)
            return d

        if not any(d is None or isinstance(d, (_DeferredCrash,
                                               _DeferredLoss))
                   for d in self.delays):
            return self
        return replace(self, delays=tuple(_bind(d) for d in self.delays))

    # -- the chain ---------------------------------------------------------
    def sequence(self, key: jax.Array, n_epochs: int) -> jax.Array:
        """(n_epochs,) int32 regime ids: z[0] = start, z[e+1] sampled from
        transition row z[e] under ``fold_in(key, e)``.  A scan prefix —
        z[e] is independent of ``n_epochs``, which is what makes regime
        assignment invariant to chunking (longer scans only append)."""
        cum = jnp.cumsum(self.transition.astype(jnp.float32), axis=1)
        r = self.n_regimes

        def step(z, e):
            u = jax.random.uniform(jax.random.fold_in(key, e), ())
            z_next = jnp.clip(
                jnp.searchsorted(cum[z], u, side="right"), 0, r - 1
            ).astype(jnp.int32)
            return z_next, z

        z0 = psharding.vary_like(jnp.int32(self.start), key)
        _, zs = jax.lax.scan(step, z0,
                             jnp.arange(n_epochs, dtype=jnp.int32))
        return zs

    def mixed_delay(self, rid: jax.Array) -> "_RegimeMixedDelay":
        """The per-sample environment selector for one chunk: ``rid`` is
        the (chunk,) regime id of each trial."""
        return _RegimeMixedDelay(models=self.delays, rid=rid)

    # -- declarative config ------------------------------------------------
    @classmethod
    def from_config(cls, cfg: Dict, n: Optional[int] = None
                    ) -> "MarkovRegimes":
        """Build from the JSON scenario-suite shape (module docstring).
        ``n`` resolves cluster-size-dependent pieces: per-regime ``crashed``
        lists and symmetric-WAN delay shorthands."""
        if isinstance(cfg, cls):
            return cfg.validate()
        entries = cfg["regimes"]
        if not entries:
            raise ValueError("regime config needs at least one regime")
        names, delays = [], []
        for i, e in enumerate(entries):
            names.append(str(e.get("name", f"regime{i}")))
            d = delay_from_config(e.get("delay"), n)
            loss = float(e.get("loss_prob", 0.0))
            crashed = tuple(e.get("crashed", ()))
            mask = None
            if crashed:
                if n is None:
                    raise ValueError(
                        f"regime {names[-1]!r} crashes acceptors "
                        f"{sorted(crashed)} but the cluster size is "
                        f"unknown; resolve the config with n=")
                m_ = np.zeros((n,), bool)
                m_[np.asarray(sorted(set(crashed)), np.int64)] = True
                mask = jnp.asarray(m_)
            if d is None:
                # loss/crashes on top of the INHERITED base delay: defer
                # the wrap until the scenario binds its model.
                if loss:
                    d = _DeferredLoss(loss, mask)
                elif mask is not None:
                    d = _DeferredCrash(mask)
            else:
                if loss:
                    d = LossyDelay(d, loss)
                if mask is not None:
                    d = CrashedDelay(d, mask)
            delays.append(d)
        out = cls(names=tuple(names), delays=tuple(delays),
                  transition=jnp.asarray(cfg["transition"], jnp.float32),
                  epoch_trials=int(cfg.get("epoch_trials",
                                           DEFAULT_EPOCH_TRIALS)),
                  start=int(cfg.get("start", 0)))
        return out.validate()

    def to_config(self) -> Dict:
        """Invert ``from_config`` (deferred base-delay wrappers serialize
        back to their declarative form)."""
        entries = []
        for name, d in zip(self.names, self.delays):
            e: Dict = {"name": name}
            e.update(_env_to_config(d))
            entries.append(e)
        return {"regimes": entries,
                "transition": np.asarray(self.transition,
                                         np.float64).tolist(),
                "epoch_trials": int(self.epoch_trials),
                "start": int(self.start)}


def _env_to_config(d) -> Dict:
    """One regime environment -> config fields (inverse of the per-entry
    build in ``from_config``)."""
    if d is None:
        return {}
    if isinstance(d, _DeferredCrash):
        return {"crashed": np.nonzero(np.asarray(d.crashed))[0].tolist()}
    if isinstance(d, _DeferredLoss):
        out = {"loss_prob": float(np.asarray(d.loss_prob))}
        if d.crashed is not None:
            out["crashed"] = np.nonzero(np.asarray(d.crashed))[0].tolist()
        return out
    return {"delay": delay_to_config(d)}


# Wrappers for regimes that modify the *inherited* base delay (loss /
# crashes on top of whatever the scenario runs): the inner model is not
# known until ``bound`` time, so they defer the wrap.
@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class _DeferredCrash:
    """Crash these acceptors on top of the scenario's base delay."""

    crashed: jax.Array

    def bind(self, base):
        return CrashedDelay(base, self.crashed)

    def tree_flatten(self):
        return (self.crashed,), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class _DeferredLoss:
    """Loss (and optionally crashes) on top of the scenario's base delay."""

    loss_prob: float
    crashed: Optional[jax.Array] = None

    def bind(self, base):
        d = LossyDelay(base, self.loss_prob)
        return CrashedDelay(d, self.crashed) if self.crashed is not None \
            else d

    def tree_flatten(self):
        return (self.loss_prob, self.crashed), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


# ---------------------------------------------------------------------------
# Per-sample environment selection inside one chunk.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class _RegimeMixedDelay:
    """Sample every hop under all R environments and select per trial.

    ``rid`` is the (S,) regime id of each sample in the chunk (S = the
    leading axis of every hop shape).  With R == 1 the single model
    samples on the UNFOLDED key — draws are bit-identical to running that
    model directly, which is the single-regime degeneracy contract.  With
    R > 1 each environment draws from its own fold-in sub-stream
    (environments stay independent even when two regimes share a model),
    and ``jnp.where`` keeps each trial's selected regime.  Sampling cost
    is R x the base model mix — the decide/reduce pipeline (the actual
    hot path) still runs once.
    """

    models: Tuple[object, ...]
    rid: jax.Array                  # (S,) int32

    def sample_hops(self, key: jax.Array, shape,
                    kind: str = PROPOSAL) -> jax.Array:
        if len(self.models) == 1:
            return self.models[0].sample_hops(key, shape, kind)
        sel = self.rid.reshape((-1,) + (1,) * (len(shape) - 1))
        out = None
        for r, m in enumerate(self.models):
            d = m.sample_hops(jax.random.fold_in(key, r), shape, kind)
            out = d if out is None else jnp.where(sel == r, d, out)
        return out

    def tree_flatten(self):
        return (self.models, self.rid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        models, rid = children
        return cls(models=tuple(models), rid=rid)


# ---------------------------------------------------------------------------
# Per-regime result: stacked StreamSummary slices + occupancy.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class RegimeStreamSummary:
    """A streamed run decomposed by regime.

    ``by_regime`` is a ``StreamSummary`` whose leaves carry a leading R
    axis — regime r's slice is a full, independently mergeable summary of
    exactly the trials the chain spent in regime r.  ``occupancy`` is the
    (R,) trial count per regime (sums to the run's total trials — the
    chunk-invariance property test pins it).  ``total()`` merges the
    slices back into the marginal summary with the integer-exact
    ``StreamSummary.merge``; the count/quantile-facing surface of
    ``StreamSummary`` is mirrored here and delegates to the total, so a
    ``RegimeStreamSummary`` drops into every consumer of a plain stream
    summary (frontier axes, ``Results``, benchmarks).
    """

    names: Tuple[str, ...]
    occupancy: jax.Array            # (R,) int32 valid trials per regime
    by_regime: "object"             # StreamSummary, leaves (R, M) / (R, M, B)

    def tree_flatten(self):
        return ((self.occupancy, self.by_regime), self.names)

    @classmethod
    def tree_unflatten(cls, aux, children):
        occupancy, by_regime = children
        return cls(names=aux, occupancy=occupancy, by_regime=by_regime)

    @property
    def n_regimes(self) -> int:
        return len(self.names)

    @property
    def precision(self) -> float:
        return self.by_regime.precision

    # -- slicing / merging -------------------------------------------------
    def regime(self, which):
        """Regime slice (by name or index) as a plain ``StreamSummary``."""
        i = which if isinstance(which, int) else self.names.index(which)
        return jax.tree_util.tree_map(lambda x: x[i], self.by_regime)

    def total(self):
        """The marginal summary: integer-exact merge across regimes."""
        return functools.reduce(
            lambda a, b: a.merge(b),
            [self.regime(i) for i in range(self.n_regimes)])

    def merge(self, other: "RegimeStreamSummary") -> "RegimeStreamSummary":
        """Combine two regime-decomposed runs (same regime set)."""
        if self.names != other.names:
            raise ValueError(f"cannot merge different regime sets "
                             f"{self.names} vs {other.names}")
        return RegimeStreamSummary(
            names=self.names,
            occupancy=self.occupancy + other.occupancy,
            by_regime=self.by_regime.merge(other.by_regime))

    # -- StreamSummary-compatible surface (delegates to the total) ---------
    @property
    def n_trials(self):
        return self.total().n_trials

    @property
    def n_fast(self):
        return self.total().n_fast

    @property
    def n_recovery(self):
        return self.total().n_recovery

    @property
    def n_undecided(self):
        return self.total().n_undecided

    @property
    def n_decided(self):
        return self.total().n_decided

    @property
    def max_ms(self):
        return self.total().max_ms

    @property
    def mean_ms(self):
        return self.total().mean_ms

    @property
    def hist(self):
        return self.total().hist

    def quantile(self, q):
        return self.total().quantile(q)

    def summary(self):
        return self.total().summary()

    # -- reporting ---------------------------------------------------------
    def report(self) -> Dict:
        """Host-side per-regime breakdown: occupancy plus each regime's
        normalized summary (scalars for M == 1, lists otherwise)."""
        def _host(v):
            a = np.asarray(v)
            return a.item() if a.size == 1 else a.tolist()

        occ = np.asarray(self.occupancy, np.int64)
        out = {"names": list(self.names), "occupancy": occ.tolist(),
               "occupancy_frac": (occ / max(int(occ.sum()), 1)).tolist(),
               "per_regime": {}}
        for i, name in enumerate(self.names):
            s = self.regime(i)
            out["per_regime"][name] = {k: _host(v)
                                       for k, v in s.summary().items()}
        return out


# ---------------------------------------------------------------------------
# Named presets (the ISSUE's baseline / degraded / partitioned / burst-crash
# vocabulary) — convenience builders for benchmarks and examples.
# ---------------------------------------------------------------------------

def gray_failure(n: int, *, epoch_trials: int = DEFAULT_EPOCH_TRIALS,
                 degraded_scale_ms: float = 0.8, loss_prob: float = 0.02,
                 partition: Sequence[int] = (0, 1, 2),
                 p_fail: float = 0.01, p_recover: float = 0.15
                 ) -> MarkovRegimes:
    """A 3-regime gray-failure chain: healthy baseline, a heavy-tailed
    lossy degradation, and a partition that crashes ``partition``.  The
    baseline inherits the scenario's delay; transitions keep the chain in
    baseline ~98% of epochs."""
    from .latency import ParetoDelay
    cfg_t = [[1.0 - 2 * p_fail, p_fail, p_fail],
             [p_recover, 1.0 - p_recover - p_fail, p_fail],
             [p_recover, 0.0, 1.0 - p_recover]]
    mask = np.zeros((n,), bool)
    mask[np.asarray(sorted(set(partition)), np.int64)] = True
    return MarkovRegimes(
        names=("baseline", "degraded", "partitioned"),
        delays=(None,
                LossyDelay(ParetoDelay(scale_ms=degraded_scale_ms),
                           loss_prob),
                _DeferredCrash(jnp.asarray(mask))),
        transition=jnp.asarray(cfg_t, jnp.float32),
        epoch_trials=epoch_trials).validate()
