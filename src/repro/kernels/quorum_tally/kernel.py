"""Pallas TPU kernels: batched quorum vote tally, and fused tally+decide.

The Monte-Carlo simulator's hot loop counts, for every simulated consensus
instance, how many acceptors voted for each candidate value — an
(instances x acceptors) -> (instances x values) histogram.  On TPU the
instance axis is tiled into VMEM blocks (the acceptor axis, n <= 128, lives
in the lane dimension) and each block computes its histogram with a
broadcast-compare + reduction on the VPU; no MXU needed.

Block shape: (BLOCK_S, n_pad) int32 in VMEM with n padded to the 128-lane
boundary; output block (BLOCK_S, n_values_pad).  For S = 10^6, n = 11,
V = 2 the working set per block is BLOCK_S * 128 * 4 B = 512 KiB at
BLOCK_S = 1024 — comfortably inside the ~16 MiB v5e VMEM alongside the
output tile.

``tally_decide`` extends the tally into the decision reduction the engine
needs anyway: per-instance winning value (argmax count, first-max tie-break),
its count, and a quorum-reached flag against a threshold ``q`` held in SMEM —
one VMEM pass instead of tally + three follow-up reductions over HBM.  The
decide columns come back packed in a single (BLOCK_S, LANE) int32 tile
(lane 0 winner, lane 1 max count, lane 2 reached) so the output keeps the
128-lane layout; the wrapper unpacks.  See DESIGN.md §3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quorum import PAD_THRESHOLD

BLOCK_S = 1024
LANE = 128


def _tally_kernel(votes_ref, out_ref, *, n: int, n_values: int):
    votes = votes_ref[...]                                   # (BS, n_pad) int32
    n_pad = votes.shape[-1]
    acc_valid = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1) < n
    # one value per iteration: compare + masked reduce over the lane axis.
    vals_pad = out_ref.shape[-1]
    cols = []
    for v in range(n_values):
        hit = jnp.where(acc_valid, (votes == v).astype(jnp.int32), 0)
        cols.append(hit.sum(axis=-1))                        # (BS,)
    for v in range(n_values, vals_pad):
        cols.append(jnp.zeros_like(cols[0]))
    out_ref[...] = jnp.stack(cols, axis=-1)                  # (BS, vals_pad)


@functools.partial(jax.jit, static_argnums=(1, 2))
def tally_votes(votes: jax.Array, n_values: int, interpret: bool = True) -> jax.Array:
    """(S, n) int32 votes in [0, n_values) -> (S, n_values) int32 counts."""
    S, n = votes.shape
    n_pad = max(LANE, ((n + LANE - 1) // LANE) * LANE)
    vals_pad = max(LANE, ((n_values + LANE - 1) // LANE) * LANE)
    s_pad = ((S + BLOCK_S - 1) // BLOCK_S) * BLOCK_S
    votes_p = jnp.full((s_pad, n_pad), -1, jnp.int32).at[:S, :n].set(
        votes.astype(jnp.int32))

    out = pl.pallas_call(
        functools.partial(_tally_kernel, n=n, n_values=n_values),
        grid=(s_pad // BLOCK_S,),
        in_specs=[pl.BlockSpec((BLOCK_S, n_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_S, vals_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, vals_pad), jnp.int32),
        interpret=interpret,
    )(votes_p)
    return out[:S, :n_values]


# ---------------------------------------------------------------------------
# Fused tally + decide.
# ---------------------------------------------------------------------------

def _tally_decide_kernel(votes_ref, q_ref, counts_ref, decide_ref,
                         *, n: int, n_values: int):
    votes = votes_ref[...]                                   # (BS, n_pad) int32
    n_pad = votes.shape[-1]
    acc_valid = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1) < n
    vals_pad = counts_ref.shape[-1]
    cols = []
    for v in range(n_values):
        hit = jnp.where(acc_valid, (votes == v).astype(jnp.int32), 0)
        cols.append(hit.sum(axis=-1))                        # (BS,)
    # running argmax over the (small, static) value axis; strict > keeps the
    # first-max tie-break of jnp.argmax.
    max_cnt = cols[0]
    winner = jnp.zeros_like(cols[0])
    for v in range(1, n_values):
        better = cols[v] > max_cnt
        winner = jnp.where(better, v, winner)
        max_cnt = jnp.maximum(max_cnt, cols[v])
    reached = (max_cnt >= q_ref[0, 0]).astype(jnp.int32)

    for v in range(n_values, vals_pad):
        cols.append(jnp.zeros_like(cols[0]))
    counts_ref[...] = jnp.stack(cols, axis=-1)               # (BS, vals_pad)

    lane = jax.lax.broadcasted_iota(jnp.int32, decide_ref.shape, 1)
    decide_ref[...] = jnp.where(
        lane == 0, winner[:, None],
        jnp.where(lane == 1, max_cnt[:, None],
                  jnp.where(lane == 2, reached[:, None], 0)))


# ---------------------------------------------------------------------------
# Masked tally: arbitrary quorum systems as (G, n) weight rows.
# ---------------------------------------------------------------------------

def _masked_tally_kernel(votes_ref, w_ref, t_ref, out_ref, *, n_values: int):
    """One VMEM pass per votes block: for every quorum row g and value v,
    does the masked weight of v's voters reach t[g]?

    The per-value hit matrix (BLOCK_S, n_pad) contracts against the resident
    (G_pad, n_pad) weight matrix on the MXU — one 128x128-friendly matmul per
    value — and the running minimum keeps the smallest satisfying value id.
    Padding is inert by construction: padded acceptor columns carry zero
    weight (and vote -1, matching no value), padded quorum rows carry
    threshold PAD_THRESHOLD (never reached).
    """
    votes = votes_ref[...]                                 # (BS, n_pad) int32
    w = w_ref[...]                                         # (G_pad, n_pad) f32
    t = t_ref[...]                                         # (1, G_pad) f32
    out = jnp.full((votes.shape[0], w.shape[0]), -1, jnp.int32)
    for v in range(n_values - 1, -1, -1):   # descending: lowest id wins
        hit = (votes == v).astype(jnp.float32)             # (BS, n_pad)
        wsum = jax.lax.dot_general(hit, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        out = jnp.where(wsum >= t, v, out)                 # (BS, G_pad)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnums=(3, 4))
def masked_tally(votes: jax.Array, weights: jax.Array, thresholds: jax.Array,
                 n_values: int, interpret: bool = True) -> jax.Array:
    """(S, n) votes x (G, n) quorum weights -> (S, G) satisfied-value ids.

    Semantics match ``ref.masked_tally``: entry (s, g) is the smallest value
    id whose voters' masked weight reaches ``thresholds[g]``, else -1.
    Weights and thresholds are traced operands (the whole mask table of a
    sweep lives in VMEM), so swapping systems never recompiles.
    """
    S, n = votes.shape
    G = weights.shape[0]
    if weights.shape != (G, n) or thresholds.shape != (G,):
        raise ValueError(f"weights {weights.shape} / thresholds "
                         f"{thresholds.shape} inconsistent with votes (S, {n})")
    n_pad = max(LANE, ((n + LANE - 1) // LANE) * LANE)
    g_pad = max(LANE, ((G + LANE - 1) // LANE) * LANE)
    s_pad = ((S + BLOCK_S - 1) // BLOCK_S) * BLOCK_S
    votes_p = jnp.full((s_pad, n_pad), -1, jnp.int32).at[:S, :n].set(
        votes.astype(jnp.int32))
    w_p = jnp.zeros((g_pad, n_pad), jnp.float32).at[:G, :n].set(
        weights.astype(jnp.float32))
    # padded rows: zero weight and an unreachable threshold -> never satisfied
    t_p = jnp.full((1, g_pad), jnp.float32(PAD_THRESHOLD)).at[0, :G].set(
        thresholds.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(_masked_tally_kernel, n_values=n_values),
        grid=(s_pad // BLOCK_S,),
        in_specs=[
            pl.BlockSpec((BLOCK_S, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((g_pad, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, g_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_S, g_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, g_pad), jnp.int32),
        interpret=interpret,
    )(votes_p, w_p, t_p)
    return out[:S, :G]


# ---------------------------------------------------------------------------
# Streaming fusion: selection network + masked tally + decide + histogram.
# ---------------------------------------------------------------------------

# Smaller trial blocks than the standalone tallies: the (BLOCK, bins_pad)
# one-hot histogram tile rides in VMEM next to the votes block.
BLOCK_STREAM = 512


def _select_sat(x, w, t, k: int, big):
    """In-register k-step selection network: earliest masked saturation of
    every quorum row, straight from *unsorted* arrivals.

    ``x (BS, n_pad)`` raw arrival times (+inf on padding lanes, so real
    entries — including LOST sentinels — are always extracted first),
    ``w (G_pad, n_pad)`` row weights, ``t (1, G_pad)`` thresholds.

    Each of the ``k`` static steps extracts the current minimum (ties to
    the lowest lane, the stable-argsort order), accumulates the selected
    acceptor's weight into every row via one MXU contraction, and records
    the extraction instant for rows that just crossed their threshold.
    After k >= the table's saturation depth (``engine.saturation_depths``)
    every saturable row has crossed, so the result equals the full-sort
    ``engine._sat_time`` — bit-identical when weights are integral (exact
    f32 partial sums; the jnp path's cumsum is then the same sequence).
    Unreached rows keep the ``big`` sentinel.  Returns the min over rows
    as a ``(BS, 1)`` column.
    """
    bs, n_pad = x.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (bs, n_pad), 1)
    csum = jnp.zeros((bs, w.shape[0]), jnp.float32)
    sat = jnp.full((bs, w.shape[0]), big, jnp.float32)
    done = jnp.zeros(csum.shape, jnp.bool_)
    for _ in range(k):
        cur = x.min(axis=-1, keepdims=True)              # (BS, 1)
        first = jnp.where(x == cur, iota, n_pad).min(axis=-1, keepdims=True)
        onehot = (iota == first).astype(jnp.float32)     # (BS, n_pad)
        wsel = jax.lax.dot_general(onehot, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        csum = csum + wsel                               # (BS, G_pad)
        newly = (csum >= t) & ~done
        sat = jnp.where(newly, cur, sat)
        done = done | newly
        x = jnp.where(iota == first, jnp.inf, x)         # extract the lane
    return sat.min(axis=-1, keepdims=True)               # (BS, 1)


def _stream_kernel(votes_ref, val_ref, arr_ref, cls_ref, w1_ref, t1_ref,
                   w2c_ref, t2c_ref, w2f_ref, t2f_ref, valid_ref,
                   hist_ref, stats_ref, *, n_values: int, k_sat: tuple,
                   precision: float, bins: int, undecided_ms: float):
    """One (system m, trial block s) grid step, everything VMEM-resident:

    * masked tally of the votes block against system m's fast-quorum rows
      (per-value MXU contraction, exactly ``_masked_tally_kernel``),
    * select the winning value's raw 2b arrival lane block and run the
      ``k_sat``-deep selection networks (``_select_sat``) for the fast,
      phase-1 and phase-2c saturation instants — the raw arrival block
      never exists in sorted form anywhere,
    * decide: winner's fast saturation, else detection + classic recovery,
    * classify fast / recovery / undecided (gated on the validity mask),
    * block-local DDSketch update: log-bucket index per decided trial, then
      a one-hot lane compare summed over the block,
    * running (M,)-shaped reductions: counts, latency sum, latency max.

    Outputs are revisited across the s grid dimension (index map pins them
    to block m), so the kernel initializes at s == 0 and accumulates after
    — the whole chunk reduces without leaving VMEM.

    Per-trial quantities stay ``(BS, 1)`` columns and every reduction runs
    over the sublane axis in f32 (exact: a block holds far fewer than 2^24
    trials): Mosaic lays 1-D vectors out in a way its reductions cannot
    re-tile, and a 1-D-to-scalar sum is refused on the TPU.
    """
    from repro.montecarlo.streaming import bucket_index
    s = pl.program_id(1)
    k1, k2c, k2f = k_sat
    big = jnp.float32(2.0 * undecided_ms)
    votes = votes_ref[...]                               # (BS, n_pad) int32
    w2f = w2f_ref[0]                                     # (G_pad, n_pad) f32
    t2f = t2f_ref[0]                                     # (1, G_pad) f32
    valid = valid_ref[...] != 0                          # (BS, 1) bool
    bs, n_pad = votes.shape

    # masked tally: smallest value id saturating any fast row (else V).
    best = jnp.full((bs, w2f.shape[0]), n_values, jnp.int32)
    for v in range(n_values - 1, -1, -1):   # descending: lowest id wins
        hit = (votes == v).astype(jnp.float32)
        wsum = jax.lax.dot_general(hit, w2f, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        best = jnp.where(wsum >= t2f, v, best)           # (BS, G_pad)
    best = best.min(axis=-1, keepdims=True)              # (BS, 1)
    reached = best < n_values
    widx = jnp.clip(best, 0, n_values - 1)

    # winner's raw per-value 2b arrival lanes: static one-hot gather over K.
    win_x = val_ref[:, 0:n_pad]
    for k in range(1, n_values):
        win_x = jnp.where(widx == k,
                          val_ref[:, k * n_pad:(k + 1) * n_pad], win_x)

    t_fast = _select_sat(win_x, w2f, t2f, k2f, big)
    t_det = _select_sat(arr_ref[...], w1_ref[0], t1_ref[0], k1, big)
    t_cls = _select_sat(cls_ref[...], w2c_ref[0], t2c_ref[0], k2c, big)
    rec = t_det + t_cls
    fast_ok = reached & (t_fast < undecided_ms)
    lat = jnp.where(fast_ok, t_fast, rec)
    und = lat >= undecided_ms
    fast = fast_ok & valid
    recb = ~fast_ok & ~und & valid
    undb = und & valid
    decided = fast | recb

    # block-local histogram: one-hot bucket compare, summed over the block.
    idx = bucket_index(lat, precision)                   # (BS, 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bs, hist_ref.shape[-1]), 1)
    onehot = ((lanes == idx) & decided).astype(jnp.float32)
    hist_blk = onehot.sum(axis=0, keepdims=True).astype(jnp.int32)

    f32 = jnp.float32
    colsum = lambda x: x.astype(f32).sum(axis=0, keepdims=True)   # (1, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape, 1)
    stat_blk = jnp.where(
        lane == 0, colsum(fast),
        jnp.where(lane == 1, colsum(recb),
                  jnp.where(lane == 2, colsum(undb),
                            jnp.where(lane == 3,
                                      colsum(jnp.where(decided, lat, 0.0)),
                                      jnp.where(lane == 4,
                                                jnp.where(decided, lat,
                                                          -jnp.inf).max(
                                                    axis=0, keepdims=True),
                                                0.0)))))

    @pl.when(s == 0)
    def _init():
        hist_ref[...] = hist_blk
        stats_ref[...] = stat_blk

    @pl.when(s != 0)
    def _accumulate():
        hist_ref[...] += hist_blk
        prev = stats_ref[...]
        stats_ref[...] = jnp.where(lane == 4, jnp.maximum(prev, stat_blk),
                                   prev + stat_blk)


@functools.partial(jax.jit, static_argnames=("n_values", "k_sat", "precision",
                                             "bins", "undecided_ms",
                                             "interpret"))
def stream_tally_decide_hist(votes: jax.Array, val_arr: jax.Array,
                             arrive: jax.Array, classic: jax.Array,
                             w1: jax.Array, t1: jax.Array,
                             w2c: jax.Array, t2c: jax.Array,
                             w2f: jax.Array, t2f: jax.Array,
                             valid: jax.Array, *, n_values: int,
                             k_sat: tuple, precision: float, bins: int,
                             undecided_ms: float, interpret: bool = True):
    """Fused sample→decide→sketch megakernel over a *raw* trial chunk.

    Takes the unsorted draw block straight from ``engine._draw_race``:

      votes   (S, n)    int32 per-acceptor 2b value ids (< 0: no vote)
      val_arr (S, K, n) f32 per-value 2b arrival times (LOST where not cast)
      arrive  (S, n)    f32 phase-1 arrival times
      classic (S, n)    f32 phase-2 classic arrival times

    plus the (M, G, n)/(M, G) mask tables for all three phases and the
    static per-phase selection depths ``k_sat = (k1, k2c, k2f)`` from
    ``engine.saturation_depths``.  Semantics of
    ``ref.stream_tally_decide_hist`` (same shapes, same bucketing).  Counts
    and histograms are bit-identical to the oracle for integral weights;
    the f32 latency sum accumulates block-by-block so it matches to float
    tolerance only.  Trial counts per call must stay below 2^24 (exact f32
    integers) — the streaming driver calls once per chunk, far below that.
    """
    S, n = votes.shape
    M, G1, _ = w1.shape
    G2c = w2c.shape[1]
    G2f = w2f.shape[1]
    K = val_arr.shape[1]
    if val_arr.shape != (S, K, n) or arrive.shape != (S, n) \
            or classic.shape != (S, n) or valid.shape != (S,) \
            or w2c.shape[::2] != (M, n) or w2f.shape[::2] != (M, n) \
            or t1.shape != (M, G1) or t2c.shape != (M, G2c) \
            or t2f.shape != (M, G2f):
        raise ValueError(
            f"inconsistent stream shapes: votes {votes.shape}, val_arr "
            f"{val_arr.shape}, arrive {arrive.shape}, classic "
            f"{classic.shape}, w1 {w1.shape}, w2c {w2c.shape}, w2f "
            f"{w2f.shape}, valid {valid.shape}")
    if S >= 2 ** 24:
        raise ValueError(f"chunk of {S} trials overflows exact f32 counts; "
                         f"stream smaller chunks")
    if len(k_sat) != 3 or not all(1 <= int(k) <= n for k in k_sat):
        raise ValueError(f"k_sat {k_sat} out of range for n={n}")
    bs = BLOCK_STREAM
    n_pad = max(LANE, ((n + LANE - 1) // LANE) * LANE)
    b_pad = max(LANE, ((bins + LANE - 1) // LANE) * LANE)
    s_pad = ((S + bs - 1) // bs) * bs
    inf = jnp.float32(jnp.inf)

    def pad_masks(w, t):
        G = w.shape[1]
        g_pad = max(LANE, ((G + LANE - 1) // LANE) * LANE)
        w_p = jnp.zeros((M, g_pad, n_pad), jnp.float32).at[:, :G, :n].set(
            w.astype(jnp.float32))
        t_p = jnp.full((M, 1, g_pad), jnp.float32(PAD_THRESHOLD)).at[
            :, 0, :G].set(t.astype(jnp.float32))
        return w_p, t_p, g_pad

    def pad_arrivals(x):
        # +inf on padding lanes/rows: never extracted before a real entry.
        return jnp.full((s_pad, n_pad), inf).at[:S, :n].set(
            x.astype(jnp.float32))

    votes_p = jnp.full((s_pad, n_pad), -1, jnp.int32).at[:S, :n].set(
        votes.astype(jnp.int32))
    val_p = jnp.full((s_pad, K, n_pad), inf).at[:S, :, :n].set(
        val_arr.astype(jnp.float32)).reshape(s_pad, K * n_pad)
    arr_p = pad_arrivals(arrive)
    cls_p = pad_arrivals(classic)
    w1_p, t1_p, g1_pad = pad_masks(w1, t1)
    w2c_p, t2c_p, g2c_pad = pad_masks(w2c, t2c)
    w2f_p, t2f_p, g2f_pad = pad_masks(w2f, t2f)
    valid_p = jnp.zeros((s_pad, 1), jnp.int32).at[:S, 0].set(
        valid.astype(jnp.int32))

    hist, stats = pl.pallas_call(
        functools.partial(_stream_kernel, n_values=n_values,
                          k_sat=tuple(int(k) for k in k_sat),
                          precision=precision, bins=bins,
                          undecided_ms=undecided_ms),
        grid=(M, s_pad // bs),
        in_specs=[
            pl.BlockSpec((bs, n_pad), lambda m, s: (s, 0)),
            pl.BlockSpec((bs, K * n_pad), lambda m, s: (s, 0)),
            pl.BlockSpec((bs, n_pad), lambda m, s: (s, 0)),
            pl.BlockSpec((bs, n_pad), lambda m, s: (s, 0)),
            pl.BlockSpec((1, g1_pad, n_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, 1, g1_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, g2c_pad, n_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, 1, g2c_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, g2f_pad, n_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((1, 1, g2f_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((bs, 1), lambda m, s: (s, 0)),
        ],
        # (M, 1, ·) outputs with a squeezed leading block dim: the block's
        # last two dims then equal the array's, as Mosaic's tiling requires
        # (a (1, b_pad) block over an (M, b_pad) array is refused).
        out_specs=[
            pl.BlockSpec((None, 1, b_pad), lambda m, s: (m, 0, 0)),
            pl.BlockSpec((None, 1, LANE), lambda m, s: (m, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, 1, b_pad), jnp.int32),
            jax.ShapeDtypeStruct((M, 1, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(votes_p, val_p, arr_p, cls_p, w1_p, t1_p, w2c_p, t2c_p, w2f_p, t2f_p,
      valid_p)
    hist, stats = hist[:, 0, :bins], stats[:, 0]
    return hist, {
        "n_fast": stats[:, 0].astype(jnp.int32),
        "n_recovery": stats[:, 1].astype(jnp.int32),
        "n_undecided": stats[:, 2].astype(jnp.int32),
        "sum_ms": stats[:, 3],
        "max_ms": stats[:, 4],
    }


@functools.partial(jax.jit, static_argnums=(1, 3))
def tally_decide(votes: jax.Array, n_values: int, q: jax.Array,
                 interpret: bool = True):
    """Fused histogram + decision: one VMEM pass over (S, n) votes.

    votes: (S, n) int32 in [0, n_values); entries < 0 count as "no vote".
    q:     scalar quorum threshold (traced — lives in SMEM, so sweeping it
           never recompiles).

    Returns ``(counts, winner, max_count, reached)``:
      counts    (S, n_values) int32 per-value vote counts
      winner    (S,) int32 argmax-count value id (first max on ties)
      max_count (S,) int32 the winner's vote count
      reached   (S,) bool  max count >= q
    """
    S, n = votes.shape
    n_pad = max(LANE, ((n + LANE - 1) // LANE) * LANE)
    vals_pad = max(LANE, ((n_values + LANE - 1) // LANE) * LANE)
    s_pad = ((S + BLOCK_S - 1) // BLOCK_S) * BLOCK_S
    votes_p = jnp.full((s_pad, n_pad), -1, jnp.int32).at[:S, :n].set(
        votes.astype(jnp.int32))
    q_arr = jnp.asarray(q, jnp.int32).reshape(1, 1)

    counts, decide = pl.pallas_call(
        functools.partial(_tally_decide_kernel, n=n, n_values=n_values),
        grid=(s_pad // BLOCK_S,),
        in_specs=[
            pl.BlockSpec((BLOCK_S, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_S, vals_pad), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_S, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, vals_pad), jnp.int32),
            jax.ShapeDtypeStruct((s_pad, LANE), jnp.int32),
        ],
        interpret=interpret,
    )(votes_p, q_arr)
    return (counts[:S, :n_values], decide[:S, 0], decide[:S, 1],
            decide[:S, 2].astype(jnp.bool_))
