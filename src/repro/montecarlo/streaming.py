"""Streaming, sharded Monte-Carlo trials: fixed memory at any trial count.

The materializing entry points in ``engine`` allocate a per-trial ``(M, S)``
array for every output, capping trials at device memory and making tail
percentiles (p99.9 — the number WAN operators actually provision for)
statistically meaningless at the trial counts that fit.  This module turns
the same per-chunk computation into a **reduction** (DESIGN.md §7):

  chunk scan      ``lax.scan`` draws, decides and *reduces* one chunk of
                  trials per step, carrying only a fixed-size summary state
                  — peak allocation is one chunk, independent of ``trials``.
  sketch          latency quantiles come from a DDSketch-style fixed-size
                  log-bucket histogram with a guaranteed relative error
                  (``precision``); bucket counts are integers, so sketch
                  merge is exact, associative and commutative.
  shard_map       the trial axis shards over the *global* device grid
                  (``parallel.sharding.trial_mesh`` over ``jax.devices()``
                  — all devices of all processes when ``jax.distributed``
                  is initialized, see ``parallel.distributed``); the
                  cross-device reduction is the summary merge (psum
                  counts/histograms, pmax maxima, count-weighted mean
                  combine), which is already a valid cross-host reduction.

``race_stream`` / ``fast_path_stream`` / ``classic_path_stream`` mirror the
materializing entry points;  ``trials <= chunk`` on a single device falls
back to the materializing path itself (same compile, bit-identical draws)
and reduces its output — the old behaviour survives as the small-T special
case.  Chunk c of a multi-chunk stream draws from ``fold_in(key, c)``;
global device d of a sharded stream re-keys through a second fold-in level,
``fold_in(fold_in(key, DEVICE_FOLD_DOMAIN), d)``, so device key streams can
never collide with chunk keys of a long unsharded stream (chunk indices and
device indices live in *disjoint* fold-in domains — DESIGN.md §10).  A
streamed run is therefore reproducible for a given (trials, chunk, global
device count) — and layout-invariant across process grids of the same
global device count: per-device trial counts and keys depend only on the
global index ``process_index * local_count + local_index``, and the merge
is integer-exact, so 2 processes x 4 devices ≡ 1 process x 8 devices
bit-for-bit on counts and histograms.

Everything is one jit per (table shape, chunking): ``trials`` and the table
contents are traced, so scaling a sweep from 10^5 to 10^7 trials or
swapping same-shape quorum systems re-enters the same compile
(``engine.TRACE_COUNTS['*_stream']``).
"""
from __future__ import annotations

import functools
import math
import warnings
from dataclasses import dataclass, replace
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as psharding

from . import engine
from .engine import MASK_KEYS, UNDECIDED_MS
from .latency import default_delay
from .regimes import REGIME_FOLD_DOMAIN, MarkovRegimes, RegimeStreamSummary

DEFAULT_CHUNK = 65536
DEFAULT_PRECISION = 0.01

# Second-level fold-in tag separating the per-device key domain from the
# per-chunk one.  Chunk c draws from fold_in(key, c) with c in [0, n_chunks);
# device d draws from fold_in(fold_in(key, DEVICE_FOLD_DOMAIN), d).  The
# old single-level scheme fold_in(key, 0x5eed + d) collided with chunk
# index 0x5eed + d of a long unsharded stream (0x5eed = 24301 < 2^20 —
# well inside real chunk counts); the extra fold-in level makes the two
# domains disjoint for ANY chunk/device index (regression-tested to
# n_chunks = 2^20 in tests/test_streaming.py).
DEVICE_FOLD_DOMAIN = 0x7FFFFFFF

# Sketch coverage: 10 us .. ~3 hours.  Latencies outside clamp to the edge
# buckets — quantile estimates stay order-correct but the relative-error
# guarantee only holds inside the range (simulated commit latencies are
# ~0.5 ms .. seconds, comfortably inside).
SKETCH_MIN_MS = 1e-2
SKETCH_MAX_MS = 1e7


def sketch_gamma(precision: float) -> float:
    """DDSketch bucket growth factor for a target relative error."""
    return (1.0 + precision) / (1.0 - precision)


def sketch_bins(precision: float) -> int:
    """Bucket count covering [SKETCH_MIN_MS, SKETCH_MAX_MS] at ``precision``
    relative error (plus the clamp bucket 0 for values below the range)."""
    if not 1e-4 <= precision <= 0.2:
        raise ValueError(f"precision (relative quantile error) must be in "
                         f"[1e-4, 0.2], got {precision}")
    g = sketch_gamma(precision)
    return int(math.ceil(math.log(SKETCH_MAX_MS / SKETCH_MIN_MS)
                         / math.log(g))) + 1


def bucket_index(x: jax.Array, precision: float) -> jax.Array:
    """Log-bucket index: bucket i > 0 covers (m0*g^(i-1), m0*g^i].

    The expression is shared verbatim with the fused Pallas kernel
    (``kernels/quorum_tally``) so both paths bucket identically.
    """
    log_g = math.log(sketch_gamma(precision))
    i = jnp.ceil(jnp.log(jnp.maximum(x, SKETCH_MIN_MS) / SKETCH_MIN_MS)
                 / log_g)
    return jnp.clip(i, 0, sketch_bins(precision) - 1).astype(jnp.int32)


def bucket_value(i: jax.Array, precision: float) -> jax.Array:
    """Representative value of bucket i: 2*m0*g^i/(g+1), the point whose
    relative distance to both bucket edges is exactly ``precision``."""
    g = sketch_gamma(precision)
    scale = SKETCH_MIN_MS * 2.0 * g / (g + 1.0)
    return scale * jnp.power(jnp.float32(g), i.astype(jnp.float32) - 1.0)


# ---------------------------------------------------------------------------
# StreamSummary: the fixed-size online state (a registered pytree).
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class StreamSummary:
    """Mergeable per-system summary of any number of streamed trials.

    All fields are per-system vectors (leading M axis); ``hist`` is the
    DDSketch bucket-count matrix over *decided* latencies, following the
    same convention as ``engine.summarize``: undecided instances are
    excluded from the latency statistics and reported as a rate.
    ``precision`` (static aux data) is the sketch's guaranteed relative
    quantile error.
    """

    n_trials: jax.Array       # (M,) int32  valid trials streamed
    n_fast: jax.Array         # (M,) int32  fast-path commits
    n_recovery: jax.Array     # (M,) int32  coordinated recoveries
    n_undecided: jax.Array    # (M,) int32  never decided (loss / crashes)
    mean_ms: jax.Array        # (M,) f32    running mean of decided latencies
    max_ms: jax.Array         # (M,) f32    running max (-inf before any)
    hist: jax.Array           # (M, B) int32 sketch bucket counts (decided)
    precision: float = DEFAULT_PRECISION

    def tree_flatten(self):
        return ((self.n_trials, self.n_fast, self.n_recovery,
                 self.n_undecided, self.mean_ms, self.max_ms, self.hist),
                self.precision)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, precision=aux)

    # -- construction ------------------------------------------------------
    @classmethod
    def zeros(cls, m: int, precision: float = DEFAULT_PRECISION
              ) -> "StreamSummary":
        z = jnp.zeros((m,), jnp.int32)
        return cls(z, z, z, z,
                   jnp.zeros((m,), jnp.float32),
                   jnp.full((m,), -jnp.inf, jnp.float32),
                   jnp.zeros((m, sketch_bins(precision)), jnp.int32),
                   precision)

    @classmethod
    def from_outcomes(cls, out: Dict[str, jax.Array],
                      precision: float = DEFAULT_PRECISION) -> "StreamSummary":
        """Reduce a materialized (M, S) outcome dict (``engine.race`` /
        ``Scenario.run`` shape) into a summary — the T <= chunk case."""
        m, s = out["latency_ms"].shape
        return cls.zeros(m, precision).update(out, jnp.ones((s,), bool))

    # -- derived -----------------------------------------------------------
    @property
    def n_decided(self) -> jax.Array:
        return self.n_fast + self.n_recovery

    @property
    def bins(self) -> int:
        return self.hist.shape[-1]

    # -- online updates ----------------------------------------------------
    def update(self, out: Dict[str, jax.Array],
               valid: jax.Array) -> "StreamSummary":
        """Absorb one chunk: ``out`` is an (M, C) outcome dict, ``valid`` a
        (C,) bool mask (False = padding trial, contributes nothing)."""
        lat = out["latency_ms"]
        v = valid[None, :]
        fast = out["reached_fast"] & v
        rec = out["recovery"] & v
        und = out["undecided"] & v
        decided = fast | rec
        add_cnt = decided.sum(axis=-1)
        add_sum = jnp.where(decided, lat, 0.0).sum(axis=-1)
        add_max = jnp.where(decided, lat, -jnp.inf).max(axis=-1)
        idx = bucket_index(lat, self.precision)
        add_hist = jax.vmap(lambda h, i, u: h.at[i].add(u))(
            jnp.zeros_like(self.hist), idx, decided.astype(self.hist.dtype))
        return self._absorb(
            n_trials=(fast | rec | und).sum(axis=-1).astype(jnp.int32),
            n_fast=fast.sum(axis=-1).astype(jnp.int32),
            n_recovery=rec.sum(axis=-1).astype(jnp.int32),
            n_undecided=und.sum(axis=-1).astype(jnp.int32),
            cnt=add_cnt.astype(jnp.float32), lat_sum=add_sum,
            lat_max=add_max, hist=add_hist)

    def _absorb(self, *, n_trials, n_fast, n_recovery, n_undecided, cnt,
                lat_sum, lat_max, hist) -> "StreamSummary":
        """Merge per-chunk aggregates (the fused kernel's output shape)."""
        n_old = self.n_decided.astype(jnp.float32)
        tot = n_old + cnt
        mean = jnp.where(tot > 0,
                         (self.mean_ms * n_old + lat_sum)
                         / jnp.maximum(tot, 1.0), 0.0)
        return replace(self,
                       n_trials=self.n_trials + n_trials,
                       n_fast=self.n_fast + n_fast,
                       n_recovery=self.n_recovery + n_recovery,
                       n_undecided=self.n_undecided + n_undecided,
                       mean_ms=mean,
                       max_ms=jnp.maximum(self.max_ms, lat_max),
                       hist=self.hist + hist)

    # -- merges ------------------------------------------------------------
    def merge(self, other: "StreamSummary") -> "StreamSummary":
        """Combine two summaries as if their trials had been one stream.
        Counts and histograms are integer sums (exact — merge is associative
        and commutative bit-for-bit); means combine count-weighted."""
        if other.precision != self.precision:
            raise ValueError(
                f"cannot merge sketches of different precision "
                f"({self.precision} vs {other.precision})")
        return self._absorb(
            n_trials=other.n_trials, n_fast=other.n_fast,
            n_recovery=other.n_recovery, n_undecided=other.n_undecided,
            cnt=other.n_decided.astype(jnp.float32),
            lat_sum=other.mean_ms * other.n_decided.astype(jnp.float32),
            lat_max=other.max_ms, hist=other.hist)

    def axis_merge(self, axis_name: str) -> "StreamSummary":
        """Cross-device merge inside ``shard_map``: psum the counts and the
        sketch, pmax the max, count-weighted psum for the mean."""
        ps = lambda x: jax.lax.psum(x, axis_name)
        n_dec = self.n_decided.astype(jnp.float32)
        tot = ps(n_dec)
        mean = jnp.where(tot > 0,
                         ps(self.mean_ms * n_dec) / jnp.maximum(tot, 1.0),
                         0.0)
        return replace(self,
                       n_trials=ps(self.n_trials), n_fast=ps(self.n_fast),
                       n_recovery=ps(self.n_recovery),
                       n_undecided=ps(self.n_undecided),
                       mean_ms=mean,
                       max_ms=jax.lax.pmax(self.max_ms, axis_name),
                       hist=ps(self.hist))

    # -- queries -----------------------------------------------------------
    def quantile(self, q) -> jax.Array:
        """Sketch quantile estimate over decided trials: within
        ``precision`` relative error of the exact empirical quantile for
        latencies inside the sketch range.  ``q`` scalar -> (M,); ``q``
        (Q,) -> (Q, M).  NaN where nothing decided."""
        qv = jnp.atleast_1d(jnp.asarray(q, jnp.float32))
        n = self.n_decided
        cum = jnp.cumsum(self.hist, axis=-1)                   # (M, B)
        rank = jnp.clip(jnp.ceil(qv[:, None] * n[None, :]),
                        1, jnp.maximum(n, 1)[None, :])         # (Q, M)
        idx = jnp.argmax(cum[None, :, :] >= rank[:, :, None], axis=-1)
        val = jnp.where(n[None, :] > 0,
                        bucket_value(idx, self.precision), jnp.nan)
        return val[0] if jnp.ndim(q) == 0 else val

    def summary(self) -> Dict[str, jax.Array]:
        """The normalized summary dict (`engine.summarize` keys, plus the
        p99.9/p99.99 that streaming trial counts make meaningful)."""
        n = jnp.maximum(self.n_trials, 1).astype(jnp.float32)
        has = self.n_decided > 0
        qs = self.quantile(jnp.array([0.5, 0.95, 0.99, 0.999, 0.9999]))
        return {
            "mean_ms": jnp.where(has, self.mean_ms, jnp.nan),
            "p50_ms": qs[0], "p95_ms": qs[1], "p99_ms": qs[2],
            "p999_ms": qs[3], "p9999_ms": qs[4],
            "max_ms": jnp.where(has, self.max_ms, jnp.nan),
            "fast_rate": self.n_fast / n,
            "recovery_rate": self.n_recovery / n,
            "undecided_rate": self.n_undecided / n,
        }


# ---------------------------------------------------------------------------
# Chunked scan driver (+ shard_map over the trial axis).
# ---------------------------------------------------------------------------

def _lat_only_outcomes(lat: jax.Array, fast: bool) -> Dict[str, jax.Array]:
    """Latency-array paths (fast_path / classic_path) as an outcome dict."""
    und = lat >= UNDECIDED_MS
    no = jnp.zeros_like(und)
    return {"latency_ms": lat, "undecided": und,
            "reached_fast": ~und if fast else no,
            "recovery": no if fast else ~und}


def _chunk_outcomes(path: str, key, table, offsets, delay, *, n, k_proposers,
                    chunk, use_kernel, k_sat=None,
                    recovery="coordinated") -> Dict[str, jax.Array]:
    if path == "race":
        return engine._race_outcomes(key, table, offsets, delay, n=n,
                                     k_proposers=k_proposers, samples=chunk,
                                     use_kernel=use_kernel, k_sat=k_sat,
                                     recovery=recovery)
    if path == "fast_path":
        return _lat_only_outcomes(
            engine._fast_path_outcomes(key, table, delay, n=n,
                                       samples=chunk, k_sat=k_sat), fast=True)
    return _lat_only_outcomes(
        engine._classic_path_outcomes(key, table, delay, n=n,
                                      samples=chunk, k_sat=k_sat),
        fast=False)


# ---------------------------------------------------------------------------
# Sort-free cardinality reductions (DESIGN.md §9): no (M, chunk) latency
# matrix is ever materialized.  Cardinality systems share their random
# structure — order statistics of one draw — so per-system chunk statistics
# are gathers from small shared tables keyed by order-statistic column
# (and, for the race, by the per-trial fast-saturation capacity).
# ---------------------------------------------------------------------------

def _card_layout(table, recovery: str = "coordinated") -> tuple:
    """Host-side static pair structure of a concrete cardinality table: the
    distinct (q1, q_rec) recovery pairs (P, 2) and each system's pair id
    (M,), where q_rec is the recovery-commit threshold of the active rule —
    q2c under coordinated recovery, q2f under uncoordinated.  Recovery
    latency depends on a system only through this pair, so P (not M)
    recovery columns cover the whole table."""
    import numpy as np
    q = np.asarray(table["q"])
    cols = [0, 1] if recovery == "coordinated" else [0, 2]
    pairs, inv = np.unique(q[:, cols], axis=0, return_inverse=True)
    return (jnp.asarray(pairs, jnp.int32),
            jnp.asarray(inv.astype(np.int32)))


def _dummy_layout() -> tuple:
    """Placeholder pair layout for paths that never read it (masked tables /
    reference path); keeps the ``_stream`` jit signature uniform."""
    return (jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32))


def _cols_card_update(state: StreamSummary, cols: jax.Array,
                      col_of_m: jax.Array, valid: jax.Array, *,
                      fast: bool) -> StreamSummary:
    """Absorb a latency chunk whose per-system latency is one of ``Kc``
    shared candidate columns: ``lat[m, c] = cols[c, col_of_m[m]]``.

    One (Kc, bins) histogram scatter (Kc * chunk updates instead of
    M * chunk) plus dense per-column sum/max reductions; every per-system
    quantity is then a gather.  Counts, histogram and max are bit-identical
    to ``state.update`` on the materialized (M, chunk) outcomes; the f32
    latency sum reduces per column, so the running mean matches to float
    tolerance only."""
    B = state.bins
    Kc = cols.shape[1]
    und = cols >= UNDECIDED_MS
    # decided trials land in their sketch bucket, undecided in slot B,
    # padding trials in slot B + 1 (dropped).
    bkey = jnp.where(und, B, bucket_index(cols, state.precision))
    bkey = jnp.where(valid[:, None], bkey, B + 1)
    flat = (jnp.arange(Kc, dtype=jnp.int32)[None, :] * (B + 2)
            + bkey).ravel()
    LH = jnp.zeros((Kc * (B + 2),), jnp.int32).at[flat].add(1)
    LH = LH.reshape(Kc, B + 2)
    rows = LH[col_of_m]                                  # (M, B + 2)
    hist = rows[:, :B]
    n_und = rows[:, B]
    n_dec = hist.sum(axis=-1)
    ok = valid[:, None] & ~und
    col_sum = jnp.where(ok, cols, 0.0).sum(axis=0)       # (Kc,)
    col_max = jnp.where(ok, cols, -jnp.inf).max(axis=0)  # (Kc,)
    zero = jnp.zeros_like(n_dec)
    n_valid = jnp.broadcast_to(valid.sum().astype(jnp.int32),
                               col_of_m.shape)
    return state._absorb(
        n_trials=n_valid,
        n_fast=n_dec if fast else zero,
        n_recovery=zero if fast else n_dec,
        n_undecided=n_und,
        cnt=n_dec.astype(jnp.float32),
        lat_sum=col_sum[col_of_m], lat_max=col_max[col_of_m], hist=hist)


def _race_card_update(state: StreamSummary, key, table, layout, offsets,
                      delay, valid, *, n, k_proposers, chunk, use_kernel,
                      k_sat, recovery="coordinated") -> StreamSummary:
    """Sort-free streamed race chunk for cardinality tables.

    The per-trial *fast capacity* ``fcap = min(max_cnt, #finite winner
    2bs)`` collapses the fast-path decision: system m commits fast exactly
    when ``fcap >= q2f_m`` (both need q2f votes AND the q2f-th winner 2b to
    arrive, and the winner-2b prefix is ascending so the q2f-th is finite
    iff at least q2f are).  Recovery latency depends on m only through its
    (q1, q2c) pair.  So one chunk reduces into:

      * FH (k2f, V, bins): winner-2b column histograms keyed by fcap slot —
        suffix-cumsum over slots, then gather at (q2f-1, q2f) per system;
      * RH (P, V, bins+1): recovery-pair histograms (undecided in the extra
        bucket) keyed by fcap slot — prefix-cumsum, gather at (pair, q2f-1);
      * matching per-slot sums (one-hot matmuls, no scatter) and maxima
        (static loop over the <= n+1 slots).

    Scatter volume drops from M * chunk to (k2f + P) * chunk updates; every
    integer output (decide bits, histogram, counts, max) is bit-identical
    to the materialized ``_decide`` + ``state.update`` path — only the f32
    latency-sum reduction order differs.

    ``recovery`` rides through unchanged: ``layout`` already pairs each
    system with the rule's commit threshold (q2c or q2f) and
    ``_sample_race`` deepens/retargets the classic presort, so the pair
    gather below is rule-agnostic.
    """
    k1, k2c, k2f = k_sat
    draws = engine._sample_race(key, offsets, delay, n=n,
                                k_proposers=k_proposers, samples=chunk,
                                use_kernel=use_kernel, k_sat=k_sat,
                                need_perms=False, recovery=recovery)
    pairs, pair_of_m = layout                            # (P, 2), (M,)
    P_ = pairs.shape[0]
    q2f = table["q"][:, 2]                               # (M,) traced
    B = state.bins
    prec = state.precision
    win = engine._win_sorted(draws)                      # (C, k2f) ascending
    V = k2f + 1                                          # fcap slots 0..k2f

    nfin = (win < UNDECIDED_MS).sum(axis=-1).astype(jnp.int32)
    fcap = jnp.minimum(draws["max_cnt"], nfin)           # (C,) in [0, k2f]
    vkey = jnp.where(valid, fcap, V)                     # V = padding slot

    # ---- fast side: winner-2b prefix columns ------------------------------
    bwin = bucket_index(win, prec)                       # (C, k2f)
    fkey = (jnp.arange(k2f, dtype=jnp.int32)[None, :] * (V + 1)
            + vkey[:, None]) * B + bwin
    FH = jnp.zeros((k2f * (V + 1) * B,), jnp.int32).at[fkey.ravel()].add(1)
    FH = FH.reshape(k2f, V + 1, B)[:, :V]                # drop padding slot
    SFH = jnp.flip(jnp.cumsum(jnp.flip(FH, 1), axis=1), 1)   # suffix over v
    hist_fast = SFH[q2f - 1, q2f]                        # (M, B)

    oh = (vkey[:, None] == jnp.arange(V, dtype=jnp.int32)[None, :]
          ).astype(jnp.float32)                          # (C, V) valid only
    # HIGHEST: on the TPU the default f32 matmul rounds its operands to
    # bf16, which would put a ~1e-3 relative error on the latency sums.
    hi = jax.lax.Precision.HIGHEST
    Fsum = jnp.einsum("cj,cv->jv", win, oh, precision=hi)    # (k2f, V)
    SFsum = jnp.flip(jnp.cumsum(jnp.flip(Fsum, 1), axis=1), 1)
    sum_fast = SFsum[q2f - 1, q2f]                       # (M,)

    # per-slot column maxima: static loop over the <= n + 1 slots.
    Fmax = jnp.stack([jnp.where((vkey == v)[:, None], win, -jnp.inf).max(0)
                      for v in range(V)], axis=1)        # (k2f, V)
    SFmax = jnp.flip(jax.lax.cummax(jnp.flip(Fmax, 1), axis=1), 1)
    max_fast = SFmax[q2f - 1, q2f]                       # (M,)

    cnt_v = jnp.zeros((V + 1,), jnp.int32).at[vkey].add(1)[:V]
    scnt = jnp.flip(jnp.cumsum(jnp.flip(cnt_v, 0)), 0)   # suffix counts
    n_fast = scnt[q2f]                                   # (M,)

    # ---- recovery side: (q1, q2c) pair columns ----------------------------
    t_rec = (jnp.take(draws["sorted_arrive"], pairs[:, 0] - 1, axis=1)
             + jnp.take(draws["sorted_classic"], pairs[:, 1] - 1, axis=1))
    dec = t_rec < UNDECIDED_MS                           # (C, P)
    brec = jnp.where(dec, bucket_index(t_rec, prec), B)  # bucket B: undecided
    rkey = (jnp.arange(P_, dtype=jnp.int32)[None, :] * (V + 1)
            + vkey[:, None]) * (B + 1) + brec
    RH = jnp.zeros((P_ * (V + 1) * (B + 1),),
                   jnp.int32).at[rkey.ravel()].add(1)
    RH = RH.reshape(P_, V + 1, B + 1)[:, :V]
    CRH = jnp.cumsum(RH, axis=1)                         # prefix over v
    rec_rows = CRH[pair_of_m, q2f - 1]                   # (M, B + 1)
    hist_rec = rec_rows[:, :B]
    n_und = rec_rows[:, B]
    n_rec = hist_rec.sum(axis=-1)

    Rsum = jnp.einsum("cp,cv->pv", jnp.where(dec, t_rec, 0.0), oh,
                      precision=hi)
    CRsum = jnp.cumsum(Rsum, axis=1)
    sum_rec = CRsum[pair_of_m, q2f - 1]

    Rmax = jnp.stack(
        [jnp.where((vkey == v)[:, None] & dec, t_rec, -jnp.inf).max(0)
         for v in range(V)], axis=1)                     # (P, V)
    CRmax = jax.lax.cummax(Rmax, axis=1)
    max_rec = CRmax[pair_of_m, q2f - 1]

    n_valid = jnp.broadcast_to(valid.sum().astype(jnp.int32), q2f.shape)
    return state._absorb(
        n_trials=n_valid, n_fast=n_fast, n_recovery=n_rec,
        n_undecided=n_und, cnt=(n_fast + n_rec).astype(jnp.float32),
        lat_sum=sum_fast + sum_rec,
        lat_max=jnp.maximum(max_fast, max_rec),
        hist=hist_fast + hist_rec)


def _race_fused_update(state: StreamSummary, key, table, offsets, delay,
                       valid, *, n, k_proposers, chunk, k_sat,
                       recovery="coordinated") -> StreamSummary:
    """Masked-table race chunk through the fused megakernel: the *raw*
    (unsorted) arrival block goes straight into the kernel, which runs the
    k_max-step selection network in-registers, then masked tally + decide +
    latency + one-hot histogram without leaving VMEM (DESIGN.md §3, §9).

    No ``(chunk, n)`` sorted array is ever materialized on this path — the
    engine contributes only the RNG draws and vote structure
    (``_draw_race``); everything system-dependent happens inside the
    kernel grid over (systems, trial blocks).

    The kernel's recovery-commit operands are positional, so uncoordinated
    recovery feeds the phase-2f masks (and the k2f prefix depth) where
    coordinated feeds phase-2c — the classic-leg draws already match the
    rule from ``_draw_race``."""
    raw = engine._draw_race(key, offsets, delay, n=n,
                            k_proposers=k_proposers, samples=chunk,
                            recovery=recovery)
    if recovery == "uncoordinated":
        rec_w, rec_t = table["p2f_w"], table["p2f_t"]
        k_sat = (k_sat[0], k_sat[2], k_sat[2])
    else:
        rec_w, rec_t = table["p2c_w"], table["p2c_t"]
    from repro.kernels.quorum_tally import ops as qt_ops
    hist, stats = qt_ops.stream_tally_decide_hist(
        raw["votes"], raw["val_arr"], raw["arrive"], raw["classic"],
        table["p1_w"], table["p1_t"], rec_w, rec_t,
        table["p2f_w"], table["p2f_t"], valid, n_values=k_proposers,
        k_sat=k_sat, precision=state.precision, bins=state.bins,
        undecided_ms=float(UNDECIDED_MS))
    return state._absorb(
        n_trials=stats["n_fast"] + stats["n_recovery"] + stats["n_undecided"],
        n_fast=stats["n_fast"], n_recovery=stats["n_recovery"],
        n_undecided=stats["n_undecided"],
        cnt=(stats["n_fast"] + stats["n_recovery"]).astype(jnp.float32),
        lat_sum=stats["sum_ms"], lat_max=stats["max_ms"], hist=hist)


# ---------------------------------------------------------------------------
# Markov-modulated regime scan (DESIGN.md §12): the chunk loop sweeps
# through failure epochs instead of one static environment.
# ---------------------------------------------------------------------------

def _regime_zeros(regimes: MarkovRegimes, m: int,
                  precision: float) -> RegimeStreamSummary:
    """The merge identity: zero occupancy, zero per-regime summaries."""
    r = regimes.n_regimes
    z = StreamSummary.zeros(m, precision)
    return RegimeStreamSummary(
        names=regimes.names,
        occupancy=jnp.zeros((r,), jnp.int32),
        by_regime=jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), z, *([z] * (r - 1))))


def _regime_device_stream(key, table, offsets, delay, trials, regimes, *,
                          path, n, k_proposers, chunk, n_chunks, n_epochs,
                          precision, use_kernel, k_sat,
                          recovery="coordinated") -> RegimeStreamSummary:
    """One device's chunked scan under a Markov regime chain.

    The chain ``zs`` is sampled up front (``n_epochs`` covers the scan's
    static trial capacity ``n_chunks * chunk``) from its own fold-in
    domain, so chunk keys are untouched.  Trial t of THIS device runs in
    regime ``zs[t // epoch_trials]`` — a pure function of the device key
    and the absolute trial index, which makes regime assignment (and
    hence occupancy counts) invariant under the ``chunk`` size.  Each
    chunk samples hops under the mixed per-trial environment, decides
    once, and scatters its outcomes into R per-regime ``StreamSummary``
    slices via the regime-selected validity masks — counts/histograms
    stay exact integers, so slices merge back to the marginal summary
    with ``StreamSummary.merge`` bit-for-bit.

    With a single regime the chain is constantly 0 and the mixed delay
    samples the base model on the unfolded chunk key: draws, decide bits,
    counts and histograms are bit-identical to the plain i.i.d. stream.
    """
    m = table["p1_w"].shape[0]
    r = regimes.n_regimes
    ep = regimes.epoch_trials
    zs = regimes.sequence(
        jax.random.fold_in(key, jnp.int32(REGIME_FOLD_DOMAIN)), n_epochs)

    def body(carry, i):
        occ, states = carry
        k = jax.random.fold_in(key, i)
        tidx = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = tidx < trials
        rid = zs[jnp.clip(tidx // ep, 0, n_epochs - 1)]
        out = _chunk_outcomes(path, k, table, offsets,
                              regimes.mixed_delay(rid), n=n,
                              k_proposers=k_proposers, chunk=chunk,
                              use_kernel=use_kernel, k_sat=k_sat,
                              recovery=recovery)
        sel = [valid & (rid == j) for j in range(r)]
        states = tuple(states[j].update(out, sel[j]) for j in range(r))
        occ = occ + jnp.stack([s.sum() for s in sel]).astype(jnp.int32)
        return (occ, states), None

    carry0 = psharding.vary_like(
        (jnp.zeros((r,), jnp.int32),
         tuple(StreamSummary.zeros(m, precision) for _ in range(r))), key)
    (occ, states), _ = jax.lax.scan(body, carry0,
                                    jnp.arange(n_chunks, dtype=jnp.int32))
    return RegimeStreamSummary(
        names=regimes.names, occupancy=occ,
        by_regime=jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), states[0], *states[1:]))


@functools.partial(jax.jit,
                   static_argnames=("path", "n", "k_proposers", "chunk",
                                    "n_chunks", "n_epochs", "precision",
                                    "use_kernel", "mesh", "k_sat",
                                    "recovery"))
def _stream(key, table, layout, offsets, delay, trials, regimes, *, path, n,
            k_proposers, chunk, n_chunks, n_epochs, precision, use_kernel,
            mesh, k_sat, recovery="coordinated"):
    engine.TRACE_COUNTS[path + "_stream"] += 1
    m = table["p1_w"].shape[0]
    # The fused-kernel and shared-column lowerings assume ONE environment
    # per chunk; a regime mix is per-trial, so regime runs keep the k_sat
    # top-k presorts but decide through the generic outcome path (whose
    # integer outputs are bit-identical by the DESIGN.md §9 contract).
    fused = (path == "race" and use_kernel and "q" not in table
             and k_sat is not None and regimes is None)
    card = "q" in table and k_sat is not None and regimes is None
    if regimes is not None:
        engine.TRACE_COUNTS[path + "_stream_regimes"] += 1
    if fused:
        engine.TRACE_COUNTS["race_stream_fused"] += 1
    elif k_sat is not None:
        engine.TRACE_COUNTS[path + "_stream_sortfree"] += 1

    def device_stream(key, table, layout, offsets, delay, trials, regimes):
        if regimes is not None:
            return _regime_device_stream(
                key, table, offsets, delay, trials, regimes, path=path,
                n=n, k_proposers=k_proposers, chunk=chunk,
                n_chunks=n_chunks, n_epochs=n_epochs, precision=precision,
                use_kernel=use_kernel, k_sat=k_sat, recovery=recovery)
        def body(state, i):
            k = jax.random.fold_in(key, i)
            valid = jnp.arange(chunk, dtype=jnp.int32) \
                < jnp.clip(trials - i * chunk, 0, chunk)
            if fused:
                state = _race_fused_update(state, k, table, offsets, delay,
                                           valid, n=n,
                                           k_proposers=k_proposers,
                                           chunk=chunk, k_sat=k_sat,
                                           recovery=recovery)
            elif card and path == "race":
                state = _race_card_update(state, k, table, layout, offsets,
                                          delay, valid, n=n,
                                          k_proposers=k_proposers,
                                          chunk=chunk,
                                          use_kernel=use_kernel,
                                          k_sat=k_sat, recovery=recovery)
            elif card and path == "fast_path":
                cols = engine._sorted_prefix(
                    engine._fast_path_draws(k, delay, n, chunk), k_sat[2])
                state = _cols_card_update(state, cols, table["q"][:, 2] - 1,
                                          valid, fast=True)
            elif card:                     # classic_path
                d0, pathv = engine._classic_path_draws(k, delay, n, chunk)
                cols = d0[:, None] + engine._sorted_prefix(pathv, k_sat[1])
                state = _cols_card_update(state, cols, table["q"][:, 1] - 1,
                                          valid, fast=False)
            else:
                out = _chunk_outcomes(path, k, table, offsets, delay, n=n,
                                      k_proposers=k_proposers, chunk=chunk,
                                      use_kernel=use_kernel, k_sat=k_sat,
                                      recovery=recovery)
                state = state.update(out, valid)
            return state, None
        state0 = psharding.vary_like(StreamSummary.zeros(m, precision), key)
        state, _ = jax.lax.scan(body, state0,
                                jnp.arange(n_chunks, dtype=jnp.int32))
        return state

    if mesh is None:
        return device_stream(key, table, layout, offsets, delay, trials,
                             regimes)

    ndev = mesh.shape[psharding.TRIAL_AXIS]

    def per_device(key, table, layout, offsets, delay, trials, regimes):
        # All per-device quantities derive from the GLOBAL device index
        # (process_index * local_count + local_index on a multi-host grid),
        # so any process layout of the same global device count runs the
        # same per-device programs and the integer-exact axis_merge makes
        # the merged summary layout-invariant bit-for-bit.
        d = jax.lax.axis_index(psharding.TRIAL_AXIS)
        t_d = trials // ndev + jnp.where(d < trials % ndev, 1, 0)
        # Second fold-in level = device key domain disjoint from chunk keys.
        k_d = jax.random.fold_in(
            jax.random.fold_in(key, jnp.int32(DEVICE_FOLD_DOMAIN)), d)
        # trials < ndev leaves trailing devices with t_d == 0: they would
        # still scan n_chunks all-invalid chunks.  Short-circuit them to
        # the zeros identity (exact under merge: counts/hist 0, max -inf)
        # — XLA runs only the taken cond branch, so empty devices launch
        # no per-chunk kernels.  The collective merge stays OUTSIDE the
        # cond: every device must participate in the psum/pmax.
        state = jax.lax.cond(
            t_d > 0,
            lambda: device_stream(key=k_d, table=table, layout=layout,
                                  offsets=offsets, delay=delay, trials=t_d,
                                  regimes=regimes),
            lambda: psharding.vary_like(
                StreamSummary.zeros(m, precision) if regimes is None
                else _regime_zeros(regimes, m, precision), k_d))
        if regimes is not None:
            # per-regime slices merge exactly like plain summaries (their
            # leaves just carry a leading R axis); occupancy is an exact
            # integer psum.
            return replace(
                state,
                occupancy=jax.lax.psum(state.occupancy,
                                       psharding.TRIAL_AXIS),
                by_regime=state.by_regime.axis_merge(psharding.TRIAL_AXIS))
        return state.axis_merge(psharding.TRIAL_AXIS)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P()),
        out_specs=P())(key, table, layout, offsets, delay, trials, regimes)


def _resolve_mesh(shard):
    """``shard=True`` -> the global trial mesh (falls back to unsharded on
    a single device, with a ``UserWarning`` so multi-process launch scripts
    that forgot ``distributed.initialize()`` / forced host devices fail
    loudly rather than quietly degrading); an explicit ``Mesh`` is honored
    as-is, 1-device included (the layout was chosen deliberately — e.g. a
    worker that must stay on the collective code path)."""
    if shard is False or shard is None:
        return None
    if shard is True:
        ndev = len(jax.devices())
        if ndev > 1:
            return psharding.trial_mesh()
        warnings.warn(
            f"shard=True but only {ndev} device is visible - running "
            f"unsharded. For a multi-process grid call "
            f"repro.parallel.distributed.initialize() before any jax use; "
            f"for local device parallelism set "
            f"--xla_force_host_platform_device_count in XLA_FLAGS; pass "
            f"shard=False to silence.", UserWarning, stacklevel=4)
        return None
    return shard                       # an explicit Mesh (any device count)


def _resolve_k_sat(table, k_max, n: int):
    """Normalize the ``k_max`` knob to a static ``(k1, k2c, k2f)`` tuple
    (or None = full-sort reference path).  ``"auto"`` derives the depths
    from the concrete table (``engine.saturation_depths``); an int caps all
    three phases; an explicit 3-tuple is clipped to [1, n]."""
    if k_max is None:
        return None
    if k_max == "auto":
        return engine.saturation_depths(table)
    if isinstance(k_max, int):
        k_max = (k_max, k_max, k_max)
    ks = tuple(int(k) for k in k_max)
    if len(ks) != 3:
        raise ValueError(f"k_max must be None, 'auto', an int or a "
                         f"(k1, k2c, k2f) triple, got {k_max!r}")
    depths = engine.saturation_depths(table)
    for req, need in zip(ks, depths):
        if req < need:
            raise ValueError(
                f"k_max={ks} below the table's saturation depths {depths}; "
                f"prefixes that short change results — use 'auto'")
    return tuple(min(n, max(1, k)) for k in ks)


def _stream_entry(path: str, key, table, delay, offsets, *, n, k_proposers,
                  trials, chunk, precision, use_kernel, shard, k_max="auto",
                  regimes=None, recovery="coordinated") -> StreamSummary:
    engine._check_mask_table(table, n)
    engine._check_recovery(recovery)
    if trials < 1:
        raise ValueError(f"trials must be >= 1, got {trials}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    sketch_bins(precision)             # validates precision
    if regimes is not None:
        if isinstance(regimes, dict):
            regimes = MarkovRegimes.from_config(regimes, n)
        regimes = regimes.validate().bound(
            delay if delay is not None else default_delay())
    mesh = _resolve_mesh(shard)
    if mesh is None and trials <= chunk and regimes is None:
        # The materializing path IS the T <= chunk special case: same
        # compile as direct engine calls, bit-identical draws, reduced.
        if path == "race":
            out = engine.race(key, table, offsets, delay, n=n,
                              k_proposers=k_proposers, samples=trials,
                              use_kernel=use_kernel, recovery=recovery)
        elif path == "fast_path":
            out = _lat_only_outcomes(
                engine.fast_path(key, table, delay, n=n, samples=trials),
                fast=True)
        else:
            out = _lat_only_outcomes(
                engine.classic_path(key, table, delay, n=n, samples=trials),
                fast=False)
        return StreamSummary.from_outcomes(out, precision)
    k_sat = _resolve_k_sat(table, k_max, n)
    layout = (_card_layout(table, recovery)
              if "q" in table and k_sat is not None else _dummy_layout())
    ndev = 1 if mesh is None else mesh.shape[psharding.TRIAL_AXIS]
    per_device = -(-trials // ndev)                # ceil: busiest device
    n_chunks = -(-per_device // chunk)
    # Regime epochs cover the scan's static per-device trial capacity, so
    # n_epochs is a pure function of the jit geometry (trials stays traced).
    n_epochs = (1 if regimes is None
                else -(-(n_chunks * chunk) // regimes.epoch_trials))
    if delay is None:
        delay = default_delay()
    offsets = (jnp.zeros((1,), jnp.float32) if offsets is None
               else jnp.asarray(offsets, jnp.float32))
    return _stream(key, table, layout, offsets, delay, jnp.int32(trials),
                   regimes, path=path, n=n, k_proposers=k_proposers,
                   chunk=chunk, n_chunks=n_chunks, n_epochs=n_epochs,
                   precision=precision, use_kernel=use_kernel, mesh=mesh,
                   k_sat=k_sat, recovery=recovery)


def race_stream(key, table, offsets, delay=None, *, n: int, k_proposers: int,
                trials: int, chunk: int = DEFAULT_CHUNK,
                precision: float = DEFAULT_PRECISION,
                use_kernel: bool = False, shard: bool = True,
                k_max="auto", regimes=None,
                recovery: str = "coordinated") -> StreamSummary:
    """``engine.race`` at any trial count in fixed memory: chunked
    ``lax.scan`` reduction into a ``StreamSummary``, trial axis sharded
    over local devices when ``shard`` (a bool or an explicit 1-D mesh).
    One compile per (table shape, chunk count); ``trials`` is traced.

    ``k_max`` (default ``"auto"``) selects the sort-free lowering
    (DESIGN.md §9): top-k arrival prefixes at the table's saturation depths
    plus, on cardinality tables, the shared-column chunk reduction — decide
    bits, histograms, counts and maxima are bit-identical to ``k_max=None``
    (the retained full-sort reference path); only the f32 mean accumulates
    in a different order.  With ``use_kernel`` on masked tables the chunk
    runs through the raw-arrivals megakernel instead (requires ``k_max``).

    ``regimes`` (a ``MarkovRegimes`` or its config dict, DESIGN.md §12)
    Markov-modulates the stream through failure epochs and returns a
    ``RegimeStreamSummary`` (per-regime slices + the merged marginal);
    ``None`` keeps the i.i.d. path bit-identical to previous behaviour.

    ``recovery`` (static, ``engine.RECOVERY_MODES``) selects the
    collision-recovery rule; each mode is its own compile of the same
    stream path (one per mode, not per system)."""
    return _stream_entry("race", key, table, delay, offsets, n=n,
                         k_proposers=k_proposers, trials=trials, chunk=chunk,
                         precision=precision, use_kernel=use_kernel,
                         shard=shard, k_max=k_max, regimes=regimes,
                         recovery=recovery)


def fast_path_stream(key, table, delay=None, *, n: int, trials: int,
                     chunk: int = DEFAULT_CHUNK,
                     precision: float = DEFAULT_PRECISION,
                     shard: bool = True, k_max="auto",
                     regimes=None) -> StreamSummary:
    """Streamed conflict-free fast path (k=1): decided instances count as
    fast-path commits, lost ones as undecided.  ``k_max`` / ``regimes`` as
    in ``race_stream``."""
    return _stream_entry("fast_path", key, table, delay, None, n=n,
                         k_proposers=1, trials=trials, chunk=chunk,
                         precision=precision, use_kernel=False, shard=shard,
                         k_max=k_max, regimes=regimes)


def classic_path_stream(key, table, delay=None, *, n: int, trials: int,
                        chunk: int = DEFAULT_CHUNK,
                        precision: float = DEFAULT_PRECISION,
                        shard: bool = True, k_max="auto",
                        regimes=None) -> StreamSummary:
    """Streamed leader-relayed classic path: decided instances count as
    recoveries (there is no fast path to reach).  ``k_max`` / ``regimes``
    as in ``race_stream``."""
    return _stream_entry("classic_path", key, table, delay, None, n=n,
                         k_proposers=1, trials=trials, chunk=chunk,
                         precision=precision, use_kernel=False, shard=shard,
                         k_max=k_max, regimes=regimes)
