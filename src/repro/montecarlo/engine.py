"""K-proposer conflict-race engine over mask-encoded quorum systems.

The paper's §5 point is that Eqs. 13/14 admit a *space* of quorum systems;
evaluating that space is this module's job.  Every entry point — ``race``,
``fast_path``, ``classic_path`` — scores a whole batch of M systems in one
call with a single XLA compile, and every batch is expressed in **one
lowering**: the membership-mask table built by ``build_mask_table``
(DESIGN.md §2).  Cardinality specs, grids, weighted voting and hand-built
explicit systems all become per-phase (M, G, n) float32 weight matrices
plus (M, G) thresholds — all traced, so same-shape tables reuse a compile.

When *every* system in a table is cardinality-encodable (single all-ones
row per phase, integral threshold), ``build_mask_table`` additionally
stores the thresholds as a ``"q"`` (M, 3) int32 entry and the entry points
select an internal specialization: each masked saturation collapses to a
k-th-order-statistic gather against presorted arrivals.  The two paths are
bit-identical on cardinality systems (guarded by the parity tests in
``tests/test_quorum_systems.py``), so the specialization is purely a
lowering choice, invisible in the results.

The trick that makes one compile possible (DESIGN.md §2): a race's random
structure — who arrives where, when, and therefore who votes for what —
does not depend on the quorum system at all.  ``_sample_race`` draws and
*pre-sorts* everything once:

  sorted per-value 2b arrivals   (S, K, n)   fast-path saturation
  sorted all-votes 2b arrivals   (S, n)      recovery detection (phase 1)
  sorted classic round trips     (S, n)      recovery commit (phase 2c)
  per-value vote counts          (S, K)      via the quorum_tally kernel

``_decide`` (cardinality specialization) and ``_decide_masked`` (general)
then reduce one system to gathers and compares over the presorted arrays,
which is what ``vmap`` maps over the table.  Work is O(sample + sort) once,
plus O(M * S) gathers — instead of M full re-runs — and every system sees
identical sampled delays (common random numbers), so cross-system
comparisons are variance-free.

All simulated clocks are milliseconds from proposer 0's submission (the
paper's instance latency).  Messages with delay >= ``latency.LOST_MS`` never
arrive: acceptors that see no proposal cast no vote, and instances that
cannot gather phase-1 votes report ``undecided``.

Every entry point materializes its per-trial arrays; for trial counts past
device memory use the chunked streaming drivers in
``repro.montecarlo.streaming`` (``race_stream`` / ``fast_path_stream`` /
``classic_path_stream``), which reduce each chunk into a fixed-size
``StreamSummary`` and shard the trial axis over devices.

Device code carries ``jax.named_scope`` names, one per layer of a trial:
``repro.sample`` (delay draws) and ``repro.decide`` (tallies, presorts,
quorum saturations); ``streaming`` adds ``repro.sketch`` and
``repro.merge``.  They change only HLO metadata, and let a profiler trace
split device time by layer.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.quorum import QuorumMasks, QuorumSpec

from . import latency as lat_mod
from .latency import LOST_MS, default_delay

BIG = LOST_MS          # a Python float: importing the engine touches no device
# latencies at or beyond this are "never happened" (lost-message sentinel
# arithmetic); shared with scenarios.py so both layers classify identically
UNDECIDED_MS = LOST_MS / 2

# Incremented at trace time inside each jitted entry point; benchmarks assert
# a full table sweep costs exactly one trace (no per-system re-jit).  The
# ``*_stream`` keys belong to the chunked drivers in ``streaming.py`` (one
# trace per (table shape, chunking) — the scan reuses it for any trials).
# The ``*_stream_sortfree`` keys count traces of the sort-free streamed
# specializations (top-k prefixes + shared-column reduction, DESIGN.md §9)
# and ``race_stream_fused`` traces of the raw-arrivals megakernel path; each
# increments alongside its base ``*_stream`` key, so "sweep == one compile"
# assertions can pin the exact lowering that ran.
TRACE_COUNTS: Dict[str, int] = {"race": 0, "fast_path": 0, "classic_path": 0,
                                "race_stream": 0, "fast_path_stream": 0,
                                "classic_path_stream": 0,
                                "race_stream_sortfree": 0,
                                "fast_path_stream_sortfree": 0,
                                "classic_path_stream_sortfree": 0,
                                "race_stream_fused": 0,
                                "race_stream_regimes": 0,
                                "fast_path_stream_regimes": 0,
                                "classic_path_stream_regimes": 0}


# ---------------------------------------------------------------------------
# Mask tables: the single quorum lowering (DESIGN.md §2).
# ---------------------------------------------------------------------------

MASK_KEYS = ("p1_w", "p1_t", "p2c_w", "p2c_t", "p2f_w", "p2f_t")


def _sys_label(system, masks: QuorumMasks) -> str:
    label = getattr(masks, "label", "") or getattr(system, "label", "")
    return label or type(system).__name__


def build_mask_table(systems: Sequence, *,
                     specialize: bool = True) -> Dict[str, jax.Array]:
    """Batch M quorum systems into one traced mask table (DESIGN.md §2).

    ``systems`` may mix ``QuorumSpec`` / ``ExplicitQuorumSystem`` /
    ``WeightedQuorumSystem`` (anything with ``to_masks()``) and raw
    ``QuorumMasks``; all must share one n.  Each phase is padded to the
    max row count with never-satisfied rows, giving a dict pytree of
    ``*_w (M, G, n)`` weight and ``*_t (M, G)`` threshold float32 arrays.
    Tables of the same shape are interchangeable without recompiling.

    When every system is cardinality-encodable (one all-ones row per phase,
    integral threshold) the table also carries ``"q"`` — the (M, 3) int32
    thresholds — and the engine entry points lower to the k-th-order-
    statistic specialization, bit-identical to the general masked path.
    ``specialize=False`` suppresses that (the parity tests use it to pit
    the two lowerings against each other)."""
    if not len(systems):
        raise ValueError("mask table needs at least one quorum system")
    masks = [s if isinstance(s, QuorumMasks) else s.to_masks()
             for s in systems]
    n = masks[0].n
    for i, m in enumerate(masks):
        if m.n != n:
            raise ValueError(
                f"mask table mixes cluster sizes: system {i} "
                f"({_sys_label(systems[i], m)}) has n={m.n} but system 0 "
                f"({_sys_label(systems[0], masks[0])}) has n={n}; "
                f"use QuorumMasks.embed() or rebuild the systems on one n")
    g1 = max(m.groups[0] for m in masks)
    g2c = max(m.groups[1] for m in masks)
    g2f = max(m.groups[2] for m in masks)
    padded = [m.pad_groups(g1, g2c, g2f) for m in masks]
    table = {k: jnp.stack([jnp.asarray(getattr(m, k), jnp.float32)
                           for m in padded])
             for k in MASK_KEYS}
    if specialize:
        qs = [m.cardinality_q() for m in masks]
        if all(q is not None for q in qs):
            table["q"] = jnp.array(qs, jnp.int32)
    return table


def _check_mask_table(table, n: int) -> None:
    if not isinstance(table, dict):
        raise TypeError(
            f"expected a build_mask_table() dict, got {type(table).__name__}; "
            f"raw (M, 3) spec tables were removed — build the table with "
            f"build_mask_table([...QuorumSpec...]) or go through "
            f"repro.api.Experiment")
    missing = [k for k in MASK_KEYS if k not in table]
    if missing:
        raise ValueError(f"mask table missing entries {missing}; "
                         f"build with build_mask_table()")
    m_rows = table["p1_w"].shape[0] if table["p1_w"].ndim == 3 else -1
    for ph in ("p1", "p2c", "p2f"):
        w, t = table[ph + "_w"], table[ph + "_t"]
        if w.ndim != 3 or w.shape[-1] != n or t.shape != w.shape[:2]:
            raise ValueError(
                f"mask table phase {ph}: weights {w.shape} / thresholds "
                f"{t.shape} not (M, G, n={n}) / (M, G)")
    if "q" in table and table["q"].shape != (m_rows, 3):
        raise ValueError(
            f"mask table 'q' specialization has shape {table['q'].shape}, "
            f"expected ({m_rows}, 3)")


def saturation_depths(table: Dict[str, jax.Array]) -> Tuple[int, int, int]:
    """Max prefix depths ``(k1, k2c, k2f)`` at which any quorum of the table
    can saturate — the ``k_max`` of the sort-free lowering (DESIGN.md §9).

    For a masked row with weights ``w`` and threshold ``t`` the adversarial
    arrival order is ascending-by-weight, so the deepest position at which
    the row can first saturate (over *every* possible arrival permutation)
    is ``#{prefix sums of sorted(w) < t} + 1``.  Rows that cannot saturate
    at all (total weight < t, e.g. group padding) are excluded: on any
    prefix of that depth they still report "not reached", exactly as on the
    full sort.  Cardinality tables reduce to the column maxima of ``q``.

    Host-side and concrete (a table is concrete at stream entry); the
    result is a static compile key for the prefix shapes.
    """
    import numpy as np
    n = int(table["p1_w"].shape[-1])

    def depth(w, t):
        w = np.asarray(w, np.float64)
        t = np.asarray(t, np.float64)
        cs = np.cumsum(np.sort(w, axis=-1), axis=-1)
        saturable = cs[..., -1] >= t
        k_row = (cs < t[..., None]).sum(axis=-1) + 1
        k_row = np.where(saturable, k_row, 0)
        return int(k_row.max()) if k_row.size else 0

    if "q" in table:
        q = np.asarray(table["q"])
        ks = (int(q[:, 0].max()), int(q[:, 1].max()), int(q[:, 2].max()))
    else:
        ks = (depth(table["p1_w"], table["p1_t"]),
              depth(table["p2c_w"], table["p2c_t"]),
              depth(table["p2f_w"], table["p2f_t"]))
    return tuple(min(n, max(1, k)) for k in ks)


@jax.named_scope("repro.decide")
def _topk_ascending(x: jax.Array, k: Optional[int]):
    """Smallest-k ascending prefix of a stable sort over the last axis, plus
    the matching permutation prefix.  ``k`` of None (or >= n) falls back to
    the full argsort — that is the retained reference path, and keeps the
    prefix path bit-identical to it by construction at k == n.

    ``lax.top_k`` breaks ties toward the lower index, the same order as a
    stable ascending argsort, so prefix values AND permutation entries match
    the full sort element-for-element (including tied LOST sentinels)."""
    n = x.shape[-1]
    if k is None or k >= n:
        perm = jnp.argsort(x, axis=-1).astype(jnp.int32)
        return jnp.take_along_axis(x, perm, axis=-1), perm
    neg, idx = jax.lax.top_k(-x, k)
    return -neg, idx.astype(jnp.int32)


@jax.named_scope("repro.decide")
def _sorted_prefix(x: jax.Array, k: Optional[int]) -> jax.Array:
    """Values-only ``_topk_ascending`` (lets XLA skip the permutation when a
    lowering consumes only order statistics)."""
    if k is None or k >= x.shape[-1]:
        return jnp.sort(x, axis=-1)
    return -jax.lax.top_k(-x, k)[0]


@jax.named_scope("repro.decide")
def _kth(sorted_x: jax.Array, k: jax.Array) -> jax.Array:
    """k-th order statistic (1-indexed, traced k) from a presorted last axis."""
    idx = jnp.clip(k - 1, 0, sorted_x.shape[-1] - 1).astype(jnp.int32)
    idx = jnp.broadcast_to(idx, sorted_x.shape[:-1])[..., None]
    return jnp.take_along_axis(sorted_x, idx, axis=-1)[..., 0]


@jax.named_scope("repro.decide")
def _counts_winner(votes: jax.Array, k_proposers: int, use_kernel: bool):
    """(S, n) votes -> ((S, K) counts, (S,) winner, (S,) max count).

    The fused Pallas tally+decide kernel does the whole n-axis reduction in
    one VMEM pass; the threshold it is handed here is a placeholder (0) since
    per-system thresholds are applied by ``_decide`` — only the
    system-independent outputs are consumed.
    """
    if use_kernel:
        from repro.kernels.quorum_tally import ops as qt_ops
        counts, winner, max_cnt, _ = qt_ops.tally_decide(votes, k_proposers,
                                                         jnp.int32(0))
    else:
        from repro.kernels.quorum_tally import ref as qt_ref
        counts, winner, max_cnt, _ = qt_ref.tally_decide(votes, k_proposers,
                                                         jnp.int32(0))
    return counts, winner, max_cnt


# Collision-recovery rules (arXiv 1710.08047): ``coordinated`` is the
# paper's §6 deployment — the coordinator detects the collision from the
# round-1 2bs (phase-1 quorum q1) and commits classically with a q2c quorum
# of round trips.  ``uncoordinated`` lets the acceptors themselves detect
# (same q1-th observation) and vote directly in the next *fast* round, so
# the learner needs a q2f quorum of one-way round-2 votes — no coordinator
# round trip.  The entry condition (fast path failed) is identical, so
# P(recovery) matches across rules; only the recovery *latency* model
# changes: threshold column q2c -> q2f and classic leg d_2a+d_2b -> d_2b.
RECOVERY_MODES = ("coordinated", "uncoordinated")


def _check_recovery(recovery: str) -> None:
    if recovery not in RECOVERY_MODES:
        raise ValueError(f"unknown recovery rule {recovery!r}; "
                         f"pick one of {RECOVERY_MODES}")


@jax.named_scope("repro.sample")
def _draw_race(key: jax.Array, offsets: jax.Array, delay, *, n: int,
               k_proposers: int, samples: int,
               recovery: str = "coordinated") -> Dict:
    """Raw race draws: RNG + vote structure only, nothing sorted.

    The presorting lowerings (``_sample_race``) and the raw-arrivals
    megakernel (``kernels/quorum_tally.stream_tally_decide_hist``) both
    start from exactly these arrays, so the two streamed paths consume
    identical sampled delays by construction."""
    K = k_proposers
    kp, kl, k2a, k2b = jax.random.split(key, 4)

    d_prop = delay.sample_hops(kp, (samples, n, K), lat_mod.PROPOSAL)
    arrival = jnp.broadcast_to(offsets, (K,)).astype(d_prop.dtype) + d_prop

    # each acceptor votes for the first proposal to arrive; no arrival at all
    # (all K lost) means no vote (-1, ignored by the tally).
    votes = jnp.argmin(arrival, axis=-1).astype(jnp.int32)        # (S, n)
    vote_time = jnp.min(arrival, axis=-1)                         # (S, n)
    voted = vote_time < UNDECIDED_MS
    votes = jnp.where(voted, votes, -1)

    d_ret = delay.sample_hops(kl, (samples, n), lat_mod.TO_LEARNER)
    arrive = jnp.where(voted, vote_time + d_ret, BIG)             # 2b @ learner
    arrive = jnp.where(arrive < UNDECIDED_MS, arrive, BIG)

    # per-value 2b arrival times, non-voters masked out.
    val_arr = jnp.where(votes[:, None, :] == jnp.arange(K)[None, :, None],
                        arrive[:, None, :], BIG)                  # (S, K, n)

    # recovery commit leg after detection.  Coordinated: one classic round
    # trip (2a out + 2b back).  Uncoordinated: the detecting acceptors vote
    # directly in the next fast round, so only the one-way 2b leg to the
    # learner remains.  Both legs are always drawn (same 4-way key split),
    # so the coordinated draws are bit-identical across modes.
    d_2a = delay.sample_hops(k2a, (samples, n), lat_mod.FROM_COORDINATOR)
    d_2b = delay.sample_hops(k2b, (samples, n), lat_mod.TO_COORDINATOR)
    classic = d_2b if recovery == "uncoordinated" else d_2a + d_2b
    classic = jnp.where(classic < UNDECIDED_MS, classic, BIG)

    return {"votes": votes, "arrive": arrive, "val_arr": val_arr,
            "classic": classic}


def _sample_race(key: jax.Array, offsets: jax.Array, delay, *, n: int,
                 k_proposers: int, samples: int, use_kernel: bool,
                 k_sat: Optional[Tuple[int, int, int]] = None,
                 need_perms: bool = True,
                 recovery: str = "coordinated") -> Dict:
    """Draw one race per sample and presort everything system-independent.

    ``k_sat = (k1, k2c, k2f)`` (static, from ``saturation_depths``) switches
    the three presorts to ``lax.top_k`` prefixes of those depths — every
    downstream gather / saturation only ever reads within the prefix, so
    results are bit-identical to the full sort (``None``, the reference
    path).  ``need_perms=False`` drops the permutations for lowerings that
    consume order statistics only (the cardinality specialization).

    Under ``recovery="uncoordinated"`` the classic leg holds one-way 2b
    hops and its commit threshold is q2f, so the classic presort deepens to
    the k2f prefix (the recovery saturation reads up to position q2f)."""
    raw = _draw_race(key, offsets, delay, n=n, k_proposers=k_proposers,
                     samples=samples, recovery=recovery)
    counts, winner, max_cnt = _counts_winner(raw["votes"], k_proposers,
                                             use_kernel)
    k1, k2c, k2f = k_sat if k_sat is not None else (None, None, None)
    if recovery == "uncoordinated":
        k2c = k2f
    out = {
        "counts": counts,                                # (S, K) int32
        "winner": winner,                                # (S,) int32
        "max_cnt": max_cnt,                              # (S,) int32
        "votes": raw["votes"],                           # (S, n) int32
    }
    if need_perms:
        # presort with explicit permutations: the cardinality specialization
        # consumes only the sorted values, but the masked decide re-weights
        # acceptors in arrival order, so argsort indices ride along (XLA
        # dead-code-eliminates whichever outputs a lowering leaves unused).
        sv, pv = _topk_ascending(raw["val_arr"], k2f)
        sa, pa = _topk_ascending(raw["arrive"], k1)
        sc, pc = _topk_ascending(raw["classic"], k2c)
        out.update(perm_val_arrive=pv, perm_arrive=pa, perm_classic=pc)
    else:
        sv = _sorted_prefix(raw["val_arr"], k2f)
        sa = _sorted_prefix(raw["arrive"], k1)
        sc = _sorted_prefix(raw["classic"], k2c)
    out.update(sorted_val_arrive=sv,      # (S, K, k2f)
               sorted_arrive=sa,          # (S, k1)
               sorted_classic=sc)         # (S, k2c)
    return out


# ---------------------------------------------------------------------------
# Cardinality specialization: k-th-order-statistic gathers.
# ---------------------------------------------------------------------------

@jax.named_scope("repro.decide")
def _win_sorted(draws: Dict) -> jax.Array:
    """(S, n) presorted 2b arrivals of each sample's winning value.  In the
    cardinality path the winner (max vote count) is system-independent, so
    this gather is computed once and shared across the whole spec table."""
    return jnp.take_along_axis(
        draws["sorted_val_arrive"], draws["winner"][:, None, None],
        axis=1)[:, 0, :]


@jax.named_scope("repro.decide")
def _decide(draws: Dict, win_sorted: jax.Array, q1: jax.Array, q_rec: jax.Array,
            q2f: jax.Array) -> Dict[str, jax.Array]:
    """Apply one (traced) threshold triple to presorted draws: gathers only.

    ``q_rec`` is the recovery-commit threshold — q2c under coordinated
    recovery (classic round trips), q2f under uncoordinated (one-way round-2
    votes); the caller picks the column to match the classic-leg draws."""
    winner = draws["winner"]
    t_fast = _kth(win_sorted, q2f)                                # (S,)
    # a fast commit needs q2f acceptor *votes* AND the learner actually
    # receiving the q2f-th 2b (lost 2bs leave t_fast at the sentinel);
    # otherwise the coordinator falls back to recovery like any collision.
    fast_ok = (draws["max_cnt"] >= q2f) & (t_fast < UNDECIDED_MS)

    t_detect = _kth(draws["sorted_arrive"], q1)
    t_recover = t_detect + _kth(draws["sorted_classic"], q_rec)

    latency = jnp.where(fast_ok, t_fast, t_recover)
    undecided = latency >= UNDECIDED_MS
    return {
        "fast_winner": jnp.where(fast_ok, winner, -1),
        "reached_fast": fast_ok,
        "recovery": ~fast_ok & ~undecided,
        "undecided": undecided,
        "latency_ms": latency,
    }


# ---------------------------------------------------------------------------
# General path: arbitrary quorum systems as masked saturations (DESIGN.md §2).
# ---------------------------------------------------------------------------

@jax.named_scope("repro.decide")
def _sat_time(sorted_x: jax.Array, perm: jax.Array, w: jax.Array,
              t: jax.Array) -> jax.Array:
    """Earliest instant some quorum row's masked arrival indicator saturates.

    ``sorted_x (..., n)`` ascending arrival times, ``perm (..., n)`` the
    argsort indices (sorted position -> acceptor id), ``w (G, n)`` weights,
    ``t (G,)`` thresholds.  Row g saturates at the first sorted position
    whose cumulative (arrival-ordered) weight reaches t[g]; its time is the
    value there — the LOST sentinel when the saturating arrival never
    happened, which downstream classifies as "not reached", exactly like the
    cardinality path's k-th order statistic.  Returns the min over rows.

    On an all-ones row with threshold q this is bit-identical to
    ``_kth(sorted_x, q)``: cumulative weight i+1 first reaches q at sorted
    position q-1.
    """
    G = w.shape[0]
    w_perm = jnp.take(w, perm, axis=1)                     # (G, ..., n)
    csum = jnp.cumsum(w_perm, axis=-1)
    ok = csum >= t.reshape((G,) + (1,) * perm.ndim)        # monotone in n
    idx = jnp.argmax(ok, axis=-1).astype(jnp.int32)        # first saturation
    reached = ok[..., -1]
    x = jnp.broadcast_to(sorted_x, csum.shape)
    tt = jnp.take_along_axis(x, idx[..., None], axis=-1)[..., 0]
    tt = jnp.where(reached, tt, BIG)
    return tt.min(axis=0)


@jax.named_scope("repro.decide")
def _masked_vote_winner(votes: jax.Array, mask_table: Dict[str, jax.Array],
                        k_proposers: int, use_kernel: bool):
    """Per-sample-per-system fast-quorum vote check: which value (if any)
    gathered a full masked phase-2f quorum of round-1 *votes*.

    All G fast rows of all M systems go through the masked-tally kernel (or
    its jnp oracle) in one flattened pass.  Returns ``winner (S, M) int32``
    (-1 when no value saturates any row) and ``reached (S, M) bool``.
    """
    M, Gf, n = mask_table["p2f_w"].shape
    w_flat = mask_table["p2f_w"].reshape(M * Gf, n)
    t_flat = mask_table["p2f_t"].reshape(M * Gf)
    if use_kernel:
        from repro.kernels.quorum_tally import ops as qt_ops
        per_q = qt_ops.masked_tally(votes, w_flat, t_flat, k_proposers)
    else:
        from repro.kernels.quorum_tally import ref as qt_ref
        per_q = qt_ref.masked_tally(votes, w_flat, t_flat, k_proposers)
    per_q = per_q.reshape(votes.shape[0], M, Gf)           # (S, M, G)
    nohit = jnp.int32(k_proposers)                         # > any value id
    best = jnp.where(per_q < 0, nohit, per_q).min(axis=-1)  # (S, M)
    reached = best < nohit
    winner = jnp.where(reached, best, -1).astype(jnp.int32)
    return winner, reached


@jax.named_scope("repro.decide")
def _decide_masked(draws: Dict, masks: Dict[str, jax.Array],
                   winner: jax.Array, reached_votes: jax.Array,
                   rec_phase: str = "p2c") -> Dict[str, jax.Array]:
    """Apply one system's (traced) quorum masks to the presorted draws.

    Mirrors ``_decide`` exactly, with each k-th-order-statistic gather
    replaced by a masked saturation over the system's quorum rows; on
    cardinality-encoded masks the two paths are bit-identical.  ``rec_phase``
    (static) names the recovery-commit quorum phase — "p2c" (coordinated) or
    "p2f" (uncoordinated), matching the classic-leg draws.
    """
    widx = jnp.clip(winner, 0, draws["sorted_val_arrive"].shape[1] - 1)
    win_sorted = jnp.take_along_axis(
        draws["sorted_val_arrive"], widx[:, None, None], axis=1)[:, 0, :]
    win_perm = jnp.take_along_axis(
        draws["perm_val_arrive"], widx[:, None, None], axis=1)[:, 0, :]
    t_fast = _sat_time(win_sorted, win_perm, masks["p2f_w"], masks["p2f_t"])
    # a fast commit needs a full masked quorum of *votes* AND the learner
    # actually receiving every 2b that saturates it (lost 2bs leave t_fast
    # at the sentinel) — the same conjunction as the cardinality path.
    fast_ok = reached_votes & (t_fast < UNDECIDED_MS)

    t_detect = _sat_time(draws["sorted_arrive"], draws["perm_arrive"],
                         masks["p1_w"], masks["p1_t"])
    t_recover = t_detect + _sat_time(draws["sorted_classic"],
                                     draws["perm_classic"],
                                     masks[rec_phase + "_w"],
                                     masks[rec_phase + "_t"])

    latency = jnp.where(fast_ok, t_fast, t_recover)
    undecided = latency >= UNDECIDED_MS
    return {
        "fast_winner": jnp.where(fast_ok, winner, -1),
        "reached_fast": fast_ok,
        "recovery": ~fast_ok & ~undecided,
        "undecided": undecided,
        "latency_ms": latency,
    }


# ---------------------------------------------------------------------------
# Entry points: one per path, each dispatching on the table's lowering.
# The un-jitted ``*_outcomes`` forms are the shared bodies: the jitted
# whole-batch entry points call them once, and the streaming drivers
# (``streaming.py``) call them once per chunk inside a ``lax.scan``.
# ---------------------------------------------------------------------------

def _race_outcomes(key: jax.Array, table: Dict[str, jax.Array],
                   offsets: jax.Array, delay, *, n: int, k_proposers: int,
                   samples: int, use_kernel: bool,
                   k_sat: Optional[Tuple[int, int, int]] = None,
                   recovery: str = "coordinated") -> Dict[str, jax.Array]:
    """One full race evaluation: sample + presort once, decide per system.
    ``k_sat`` (static) presorts top-k prefixes instead of full sorts —
    bit-identical when it upper-bounds the table's saturation depths
    (``saturation_depths``); ``None`` keeps the full-sort reference path."""
    if delay is None:
        delay = default_delay()
    draws = _sample_race(key, offsets, delay, n=n, k_proposers=k_proposers,
                         samples=samples, use_kernel=use_kernel, k_sat=k_sat,
                         need_perms="q" not in table, recovery=recovery)
    rec_col = 1 if recovery == "coordinated" else 2
    if "q" in table:            # cardinality specialization: gathers only
        win_sorted = _win_sorted(draws)
        return jax.vmap(lambda q: _decide(draws, win_sorted, q[0], q[rec_col],
                                          q[2]))(table["q"])
    winner, reached = _masked_vote_winner(draws["votes"], table,
                                          k_proposers, use_kernel)
    masks = {k: table[k] for k in MASK_KEYS}
    rec_phase = "p2c" if recovery == "coordinated" else "p2f"
    return jax.vmap(lambda m, w, r: _decide_masked(draws, m, w, r, rec_phase),
                    in_axes=(0, 1, 1))(masks, winner, reached)


@functools.partial(jax.jit, static_argnames=("n", "k_proposers", "samples",
                                             "use_kernel", "recovery"))
def _race(key: jax.Array, table: Dict[str, jax.Array], offsets: jax.Array,
          delay, *, n: int, k_proposers: int, samples: int,
          use_kernel: bool,
          recovery: str = "coordinated") -> Dict[str, jax.Array]:
    TRACE_COUNTS["race"] += 1
    return _race_outcomes(key, table, offsets, delay, n=n,
                          k_proposers=k_proposers, samples=samples,
                          use_kernel=use_kernel, recovery=recovery)


def race(key: jax.Array, table, offsets: jax.Array, delay=None, *, n: int,
         k_proposers: int, samples: int, use_kernel: bool = False,
         recovery: str = "coordinated") -> Dict[str, jax.Array]:
    """K proposals race for one instance, scored under M quorum systems at
    once.

    key      PRNG key (delays are shared across systems — common random
             numbers, so system-vs-system deltas carry no sampling noise)
    table    ``build_mask_table`` dict — per-phase (M, G, n) weights and
             (M, G) thresholds, all traced: same-shape tables reuse one
             compile.  All-cardinality tables carry a ``"q"`` entry and
             lower to k-th-order-statistic gathers (bit-identical).  A raw
             (M, 3) threshold array is still accepted but deprecated.
    offsets  (K,) proposer submission times in ms (traced)
    delay    a ``repro.montecarlo.latency`` model (traced pytree)
    recovery collision-recovery rule (static): "coordinated" (classic q2c
             round trip, the default) or "uncoordinated" (q2f one-way
             round-2 votes, arXiv 1710.08047).  The fast path and the
             recovery *entry* condition are identical across rules — only
             the recovery commit latency changes.

    Returns per-system-per-sample arrays, each (M, S):
      fast_winner   proposer id that won on the fast path, -1 otherwise
      reached_fast  some value gathered a full fast phase-2 quorum of votes
      recovery      collision recovery decided the instance
      undecided     not enough votes ever arrived (message loss)
      latency_ms    decision latency from proposer 0's submission
    """
    _check_mask_table(table, n)
    _check_recovery(recovery)
    return _race(key, table, offsets, delay, n=n, k_proposers=k_proposers,
                 samples=samples, use_kernel=use_kernel, recovery=recovery)


@jax.named_scope("repro.sample")
def _fast_path_draws(key: jax.Array, delay, n: int,
                     samples: int) -> jax.Array:
    """(S, n) conflict-free client -> acceptor -> learner path times, lost
    hops at the sentinel.  Shared by both ``fast_path`` lowerings so they
    draw identical delays by construction (the bit-identity contract rests
    on it)."""
    k1, k2 = jax.random.split(key)
    d1 = delay.sample_hops(k1, (samples, n, 1), lat_mod.PROPOSAL)[..., 0]
    d2 = delay.sample_hops(k2, (samples, n), lat_mod.TO_LEARNER)
    path = d1 + d2
    return jnp.where(path < UNDECIDED_MS, path, BIG)   # lost => never arrives


def _fast_path_outcomes(key: jax.Array, table: Dict[str, jax.Array], delay,
                        *, n: int, samples: int,
                        k_sat: Optional[Tuple[int, int, int]] = None
                        ) -> jax.Array:
    if delay is None:
        delay = default_delay()
    k2f = k_sat[2] if k_sat is not None else None
    path = _fast_path_draws(key, delay, n, samples)
    if "q" in table:
        srt = _sorted_prefix(path, k2f)
        return jax.vmap(lambda q: _kth(srt, q[2]))(table["q"])
    srt, perm = _topk_ascending(path, k2f)
    return jax.vmap(lambda m: _sat_time(srt, perm, m["p2f_w"], m["p2f_t"]))(
        {k: table[k] for k in MASK_KEYS})


@functools.partial(jax.jit, static_argnames=("n", "samples"))
def _fast_path(key: jax.Array, table: Dict[str, jax.Array], delay, *,
               n: int, samples: int) -> jax.Array:
    TRACE_COUNTS["fast_path"] += 1
    return _fast_path_outcomes(key, table, delay, n=n, samples=samples)


def fast_path(key: jax.Array, table, delay=None, *, n: int,
              samples: int) -> jax.Array:
    """(M, S) conflict-free fast-path commit latencies: the saturation
    instant of each system's phase-2f quorums over the client -> acceptor
    -> learner paths (the q2f-th order statistic on cardinality tables);
    one compile for the whole table."""
    _check_mask_table(table, n)
    return _fast_path(key, table, delay, n=n, samples=samples)


@jax.named_scope("repro.sample")
def _classic_path_draws(key: jax.Array, delay, n: int, samples: int):
    """((S,) client->leader hop, (S, n) leader round-trip times); shared by
    the materializing and streamed classic-path lowerings."""
    k0, k1, k2 = jax.random.split(key, 3)
    d0 = delay.sample_hops(k0, (samples,), lat_mod.CLIENT_TO_LEADER)
    d1 = delay.sample_hops(k1, (samples, n), lat_mod.FROM_COORDINATOR)
    d2 = delay.sample_hops(k2, (samples, n), lat_mod.TO_COORDINATOR)
    path = d1 + d2
    return d0, jnp.where(path < UNDECIDED_MS, path, BIG)  # lost => never


def _classic_path_outcomes(key: jax.Array, table: Dict[str, jax.Array],
                           delay, *, n: int, samples: int,
                           k_sat: Optional[Tuple[int, int, int]] = None
                           ) -> jax.Array:
    if delay is None:
        delay = default_delay()
    k2c = k_sat[1] if k_sat is not None else None
    d0, path = _classic_path_draws(key, delay, n, samples)
    with jax.named_scope("repro.decide"):
        if "q" in table:
            srt = _sorted_prefix(path, k2c)
            return jax.vmap(lambda q: d0 + _kth(srt, q[1]))(table["q"])
        srt, perm = _topk_ascending(path, k2c)
        return jax.vmap(lambda m: d0 + _sat_time(srt, perm, m["p2c_w"],
                                                 m["p2c_t"]))(
            {k: table[k] for k in MASK_KEYS})


@functools.partial(jax.jit, static_argnames=("n", "samples"))
def _classic_path(key: jax.Array, table: Dict[str, jax.Array], delay, *,
                  n: int, samples: int) -> jax.Array:
    TRACE_COUNTS["classic_path"] += 1
    return _classic_path_outcomes(key, table, delay, n=n, samples=samples)


def classic_path(key: jax.Array, table, delay=None, *, n: int,
                 samples: int) -> jax.Array:
    """(M, S) leader-relayed classic commit latencies (phase-2c quorum
    saturation after the client -> leader hop)."""
    _check_mask_table(table, n)
    return _classic_path(key, table, delay, n=n, samples=samples)


# ---------------------------------------------------------------------------
# Summaries.
# ---------------------------------------------------------------------------

def summarize(out, axis: int = -1) -> Dict[str, jax.Array]:
    """Latency quantiles over the sample axis; works on (S,) or (M, S).

    ``out`` may be a raw latency array or an outcome dict as returned by
    ``race`` / ``Scenario.run``.  For dicts, instances that never decided
    (message loss / crashes) are *excluded* from the latency statistics —
    they would otherwise drag the LOST_MS sentinel into every quantile —
    and reported separately as ``undecided_rate``, alongside
    ``fast_rate``/``recovery_rate`` decide-bit rates."""
    if isinstance(out, dict):
        lat = jnp.where(out["undecided"], jnp.nan, out["latency_ms"])
        extra = {
            "fast_rate": out["reached_fast"].mean(axis=axis),
            "recovery_rate": out["recovery"].mean(axis=axis),
            "undecided_rate": out["undecided"].mean(axis=axis),
        }
    else:
        lat, extra = out, {}
    q = jnp.nanquantile(lat, jnp.array([0.5, 0.95, 0.99, 0.999, 0.9999]),
                        axis=axis)
    return {
        "mean_ms": jnp.nanmean(lat, axis=axis),
        "p50_ms": q[0],
        "p95_ms": q[1],
        "p99_ms": q[2],
        "p999_ms": q[3],
        "p9999_ms": q[4],
        "max_ms": jnp.nanmax(lat, axis=axis),
        **extra,
    }
