"""Plain reference for the scored quorum-space batch.

It imports nothing of the program.  What it shares with the program is
the specification of the data: the delays are drawn from the batch seed
by the documented key schedule (``jax.random`` threefry keys, one
``fold_in`` per chunk, a second ``fold_in`` level per device on a trial
mesh), so both sides score the same trials.  From those draws it decides
every trial of every system by the protocol's own rule, written out
plainly:

* a fast round commits when some value's 2b messages reach the learner
  from a fast (phase-2f) quorum; its latency is the instant the last
  needed 2b arrives;
* otherwise the coordinator detects the collision once a phase-1 quorum
  of 2bs has arrived and commits with a classic round trip to a phase-2c
  quorum (phase-2f one-way votes under uncoordinated recovery);
* a quorum row is met at the first arrival time at which the weight of
  acceptors arrived so far reaches its threshold, found by comparing
  every pair of arrivals (no sorting, no order statistics);
* a trial whose latency reaches ``LOST_MS`` never decided.

Quantiles are exact order statistics of the decided latencies.  ``dtype``
is the arithmetic precision; the control runs it in bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

LOST_MS = 5e8          # a path or latency at or past this never arrived
DEVICE_DOMAIN = 0x7FFFFFFF
BLOCK_ELEMENTS = 200_000_000     # latencies held per block of systems
STEP_ELEMENTS = 1 << 24          # (trials, rows, n) per step of a block
QUANTILES = {"fast_p50": ("fast", 0.5), "race_p50": ("race", 0.5),
             "race_p999": ("race", 0.999)}


# ---------------------------------------------------------------------------
# Delays, as the configuration states them.
# ---------------------------------------------------------------------------

def _base_ms(delay: Dict, kind: str, n: int, k: int) -> np.ndarray:
    """Deterministic propagation part of a hop, broadcastable to its
    shape: (n, K) for proposals, (n,) for acceptor <-> learner hops."""
    if delay["kind"] == "lognormal":
        return np.zeros((n, k) if kind == "proposal" else (n,), np.float32)
    r = int(delay["n_regions"])
    k_place = int(delay["k_proposers"])
    far = float(delay["inter_region_ms"])
    acc = np.arange(n) % r
    if kind == "proposal":
        prop = (np.arange(k) % k_place) % r
        return np.where(acc[:, None] == prop[None, :], 0.0, far
                        ).astype(np.float32)
    return np.where(acc == 0, 0.0, far).astype(np.float32)


def _hop(form: str, ops: Dict, key, shape, kind: str, dt):
    z = jax.random.normal(key, shape, jnp.float32).astype(dt)
    p = ops["p"]
    if form == "lognormal":
        return p[0].astype(dt) + jnp.exp(p[1].astype(dt)
                                         + p[2].astype(dt) * z)
    jitter = jnp.exp(p[0].astype(dt) + p[1].astype(dt) * z)
    return jnp.broadcast_to(ops["base"][kind].astype(dt), shape) + jitter


def _delay_operands(delay: Dict, n: int, k: int) -> Dict:
    """The traced numbers of a delay config (its ``kind`` stays static)."""
    if delay["kind"] == "lognormal":
        return {"p": jnp.array([delay["base_ms"], delay["mu"],
                                delay["sigma"]], jnp.float32)}
    if delay["kind"] != "wan":
        raise ValueError(f"reference has no delay kind {delay['kind']!r}")
    base = {kind: jnp.asarray(_base_ms(delay, kind, n, k))
            for kind in ("proposal", "to_learner", "from_coordinator",
                         "to_coordinator")}
    base["proposal1"] = jnp.asarray(_base_ms(delay, "proposal", n, 1))
    return {"p": jnp.array([delay["jitter_mu"], delay["jitter_sigma"]],
                           jnp.float32), "base": base}


# ---------------------------------------------------------------------------
# Draws: the documented key schedule.
# ---------------------------------------------------------------------------

def _device_trials(trials: int, chunk: int, ndev: int):
    """[(device index or None, trials on it)] and chunks per device."""
    if ndev == 1:
        return [(None, trials)], -(-trials // chunk)
    per = -(-trials // ndev)
    return ([(d, trials // ndev + (1 if d < trials % ndev else 0))
             for d in range(ndev)], -(-per // chunk))


def _chunk_keys(k_pass, device: Optional[int], n_chunks: int):
    base = k_pass
    if device is not None:
        base = jax.random.fold_in(
            jax.random.fold_in(k_pass, jnp.int32(DEVICE_DOMAIN)),
            jnp.int32(device))
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(n_chunks, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("form", "n", "k", "chunk",
                                             "dt", "recovery"))
def _race_draws(keys, offsets, ops, *, form, n, k, chunk, dt, recovery):
    hop = functools.partial(_hop, form, ops)

    def one(key):
        kp, kl, k2a, k2b = jax.random.split(key, 4)
        prop = hop(kp, (chunk, n, k), "proposal", dt)
        arrival = offsets.astype(dt) + prop
        votes = jnp.argmin(arrival, axis=-1)
        vote_at = jnp.min(arrival, axis=-1)
        voted = vote_at < LOST_MS
        back = hop(kl, (chunk, n), "to_learner", dt)
        arrive = jnp.where(voted, vote_at + back, jnp.inf)
        arrive = jnp.where(arrive < LOST_MS, arrive, jnp.inf).astype(dt)
        by_value = jnp.where(votes[:, None, :] == jnp.arange(k)[None, :, None],
                             arrive[:, None, :], jnp.inf).astype(dt)
        out2a = hop(k2a, (chunk, n), "from_coordinator", dt)
        in2b = hop(k2b, (chunk, n), "to_coordinator", dt)
        classic = in2b if recovery == "uncoordinated" else out2a + in2b
        classic = jnp.where(classic < LOST_MS, classic, jnp.inf).astype(dt)
        return arrive, by_value, classic
    a, v, c = jax.vmap(one)(keys)
    return (a.reshape(-1, n), v.reshape(-1, k, n), c.reshape(-1, n))


@functools.partial(jax.jit, static_argnames=("form", "n", "chunk", "dt"))
def _fast_draws(keys, ops, *, form, n, chunk, dt):
    """The conflict-free pass: one proposer (proposer 0's placement)."""
    one_prop = dict(ops)
    if form == "wan":
        one_prop["base"] = dict(ops["base"], proposal=ops["base"]["proposal1"])

    def one(key):
        k1, k2 = jax.random.split(key)
        out = _hop(form, one_prop, k1, (chunk, n, 1), "proposal", dt)[..., 0]
        back = _hop(form, ops, k2, (chunk, n), "to_learner", dt)
        path = out + back
        return jnp.where(path < LOST_MS, path, jnp.inf).astype(dt)
    return jax.vmap(one)(keys).reshape(-1, n)


def draws(seed: int, *, n: int, k: int, delta_ms: float, delay: Dict,
          trials: int, chunk: int, ndev: int, recovery: str, dt) -> Dict:
    """Every trial slot of both passes of one batch, with its validity."""
    if ndev == 1 and trials <= chunk:
        raise ValueError("the reference covers the chunked stream only: "
                         "trials must exceed chunk")
    ops = _delay_operands(delay, n, k)
    key = jax.random.PRNGKey(seed)
    k_fast, k_race = jax.random.split(key)
    offsets = delta_ms * jnp.arange(k, dtype=jnp.float32)
    plan, n_chunks = _device_trials(trials, chunk, ndev)
    parts: Dict[str, List] = {"arrive": [], "by_value": [], "classic": [],
                              "fast": [], "valid": []}
    for device, t_d in plan:
        a, v, c = _race_draws(_chunk_keys(k_race, device, n_chunks), offsets,
                              ops, form=delay["kind"], n=n, k=k, chunk=chunk,
                              dt=dt, recovery=recovery)
        f = _fast_draws(_chunk_keys(k_fast, device, n_chunks), ops,
                        form=delay["kind"], n=n, chunk=chunk, dt=dt)
        parts["arrive"].append(a)
        parts["by_value"].append(v)
        parts["classic"].append(c)
        parts["fast"].append(f)
        parts["valid"].append(jnp.arange(n_chunks * chunk) < t_d)
    return {name: jnp.concatenate(x) for name, x in parts.items()}


# ---------------------------------------------------------------------------
# Decisions.
# ---------------------------------------------------------------------------

def _met_at(x, w, t):
    """(B, n) arrival times, (R, n) weights, (R,) thresholds -> (B, R):
    the first arrival time at which a row's arrived weight reaches its
    threshold (inf when it never does)."""
    arrived_by = (x[:, None, :] <= x[:, :, None]).astype(jnp.bfloat16)
    weight = jnp.einsum("tab,rb->tra", arrived_by, w,
                        preferred_element_type=jnp.float32)
    met = weight >= t[None, :, None]
    return jnp.where(met, x[:, None, :], jnp.inf).min(axis=-1)


def _quorum_at(x, w, t, m):
    """min over each system's rows: (B, n) -> (B, M)."""
    g = t.shape[0] // m
    return _met_at(x, w, t).reshape(x.shape[0], m, g).min(axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "rec"))
def _decide(arrive, by_value, classic, fast, valid, rows, *, m, rec):
    """Latencies (inf where undecided or padding) of both passes and the
    race's fast flag, for one block of systems: each (M, T)."""
    rows_max = max(t.shape[0] for _, t in rows.values())
    want = max(1, STEP_ELEMENTS // (rows_max * arrive.shape[1]))
    tb = math.gcd(arrive.shape[0], 1 << (want.bit_length() - 1))
    nb = arrive.shape[0] // tb

    def block(xs):
        a, v, c, f, ok = xs
        t_fast = jnp.min(jnp.stack(
            [_quorum_at(v[:, j], *rows["p2f"], m)
             for j in range(v.shape[1])]), axis=0)
        fast_ok = t_fast < LOST_MS
        t_rec = _quorum_at(a, *rows["p1"], m) + _quorum_at(c, *rows[rec], m)
        lat = jnp.where(fast_ok, t_fast, t_rec)
        decided = (lat < LOST_MS) & ok[:, None]
        f_lat = _quorum_at(f, *rows["p2f"], m)
        f_dec = (f_lat < LOST_MS) & ok[:, None]
        return (jnp.where(decided, lat, jnp.inf),
                fast_ok & ok[:, None],
                jnp.where(f_dec, f_lat, jnp.inf))

    split = lambda x: x.reshape((nb, tb) + x.shape[1:])
    out = jax.lax.map(block, tuple(map(split, (arrive, by_value, classic,
                                               fast, valid))))
    return tuple(o.reshape((nb * tb,) + o.shape[2:]).T for o in out)


@jax.jit
def _counts(race_lat, fast_flag, fast_lat):
    dec = jnp.isfinite(race_lat)
    n_fast = fast_flag.sum(axis=1)
    return {"race_decided": dec.sum(axis=1), "race_fast": n_fast,
            "fast_decided": jnp.isfinite(fast_lat).sum(axis=1)}


@functools.partial(jax.jit, static_argnames=("precision", "lo", "hi"))
def _order_stats_and_hist(lat, idx, *, precision, lo, hi):
    """Order statistics at ``idx`` of each row of ``lat``, and each row's
    histogram over the sketch's log buckets: bucket i > 0 holds
    (lo g^(i-1), lo g^i] with g = (1+p)/(1-p), bucket 0 everything up to
    lo, the last bucket everything from its lower edge up."""
    srt = jnp.sort(lat, axis=1)
    bins = sketch_bins(precision, lo, hi)
    log_g = math.log((1 + precision) / (1 - precision))
    x = srt.astype(jnp.float32)        # bucket arithmetic stays in float32
    b = jnp.ceil(jnp.log(jnp.maximum(x, lo) / lo) / log_g)
    b = jnp.where(jnp.isfinite(x), jnp.clip(b, 0, bins - 1), bins)
    edges = jax.vmap(lambda row: jnp.searchsorted(
        row, jnp.arange(bins + 1, dtype=jnp.float32)))(b)
    return (jnp.take_along_axis(srt, idx, axis=1),
            jnp.diff(edges, axis=1).astype(jnp.int32))


def sketch_bins(precision: float, lo: float, hi: float) -> int:
    g = (1 + precision) / (1 - precision)
    return int(math.ceil(math.log(hi / lo) / math.log(g))) + 1


def _ranks(q: float, n_dec: np.ndarray, slack: int) -> np.ndarray:
    """0-based positions of the rank-ceil(q n) order statistic and its
    ``slack`` neighbours on either side, clipped to the decided trials."""
    rank = np.clip(np.ceil(q * n_dec.astype(np.float64)), 1,
                   np.maximum(n_dec, 1)).astype(np.int64)
    offs = np.arange(-slack, slack + 1)
    return np.clip(rank[:, None] - 1 + offs[None, :], 0,
                   np.maximum(n_dec - 1, 0)[:, None]).astype(np.int32)


def score(seed: int, rows: Dict, *, n: int, k: int, delta_ms: float,
          delay: Dict, trials: int, chunk: int, ndev: int, recovery: str,
          sketch: Dict, dtype=jnp.float32,
          rank_slack: int = 1) -> Dict[str, np.ndarray]:
    """Reference counts, exact quantiles and sketch histograms of one
    batch.

    Returns per system: ``trials``, ``race_fast``, ``race_recovery``,
    ``race_undecided``, ``fast_decided``, ``fast_undecided`` (int64);
    ``race_hist`` and ``fast_hist`` (M, bins) decided latencies per sketch
    bucket (``sketch`` gives ``precision``, ``min_ms`` and ``max_ms``);
    and, for each of ``QUANTILES``, an (M, 2 * rank_slack + 1) float64
    array of the order statistics around its rank (NaN where nothing
    decided)."""
    dt = jnp.dtype(dtype)
    d = draws(seed, n=n, k=k, delta_ms=delta_ms, delay=delay, trials=trials,
              chunk=chunk, ndev=ndev, recovery=recovery, dt=dt)
    m_all = rows["p1"][0].shape[0]
    t_slots = d["arrive"].shape[0]
    n_blocks = -(-m_all // max(1, BLOCK_ELEMENTS // t_slots))
    mb = -(-m_all // n_blocks)
    rec = "p2f" if recovery == "uncoordinated" else "p2c"
    n_trials = int(d["valid"].sum())
    out: Dict[str, List] = {name: [] for name in
                            ("race_decided", "race_fast", "fast_decided",
                             "race_hist", "fast_hist", *QUANTILES)}
    sk = dict(precision=float(sketch["precision"]),
              lo=float(sketch["min_ms"]), hi=float(sketch["max_ms"]))
    for b in range(n_blocks):
        lo, hi = b * mb, min(m_all, (b + 1) * mb)
        blk = {}
        for ph, (w, t) in rows.items():
            wb = np.zeros((mb,) + w.shape[1:], np.int32)
            tb = np.full((mb,) + t.shape[1:], np.inf, np.float32)
            wb[:hi - lo], tb[:hi - lo] = w[lo:hi], t[lo:hi]
            blk[ph] = (jnp.asarray(wb.reshape(-1, n), jnp.bfloat16),
                       jnp.asarray(tb.reshape(-1)))
        race_lat, fast_flag, fast_lat = _decide(
            d["arrive"], d["by_value"], d["classic"], d["fast"], d["valid"],
            blk, m=mb, rec=rec)
        c = {key: np.asarray(v)[:hi - lo].astype(np.int64)
             for key, v in _counts(race_lat, fast_flag, fast_lat).items()}
        for key, v in c.items():
            out[key].append(v)
        pad = lambda x: np.concatenate(
            [x, np.zeros((mb - x.shape[0],) + x.shape[1:], x.dtype)])
        for which, lat in (("race", race_lat), ("fast", fast_lat)):
            n_dec = c[which + "_decided"]
            names = [nm for nm, (w, _) in QUANTILES.items() if w == which]
            idx = np.concatenate([_ranks(QUANTILES[nm][1], n_dec, rank_slack)
                                  for nm in names], axis=1)
            vals, hist = _order_stats_and_hist(lat, jnp.asarray(pad(idx)),
                                               **sk)
            vals = np.asarray(vals, np.float64)[:hi - lo]
            vals[n_dec == 0] = np.nan
            for j, nm in enumerate(names):
                width = 2 * rank_slack + 1
                out[nm].append(vals[:, j * width:(j + 1) * width])
            out[which + "_hist"].append(
                np.asarray(hist)[:hi - lo].astype(np.int64))
        del race_lat, fast_flag, fast_lat
    res = {key: np.concatenate(v) for key, v in out.items()}
    res["trials"] = np.full(m_all, n_trials, np.int64)
    res["race_recovery"] = res["race_decided"] - res["race_fast"]
    res["race_undecided"] = n_trials - res["race_decided"]
    res["fast_undecided"] = n_trials - res["fast_decided"]
    return res


# ---------------------------------------------------------------------------
# Frontier.
# ---------------------------------------------------------------------------

def frontier_mask(values: np.ndarray, precision: float,
                  trials: int) -> np.ndarray:
    """Pareto membership of (M, 6) rows (fast_p50, race_p999, p_recovery
    minimized; three crash budgets maximized).  Latencies compare on the
    sketch's log grid of ratio (1+p)/(1-p), cells centred on bucket
    representatives; p_recovery on steps of 3 binomial sigmas at the
    trial count; crash budgets exactly.  Never-decided (NaN) is worst."""
    v = np.asarray(values, np.float64)
    g = math.log((1 + precision) / (1 - precision))
    step = 3.0 * math.sqrt(0.25 / max(trials, 1))
    with np.errstate(invalid="ignore", divide="ignore"):
        cells = np.stack([
            -np.floor(np.log(np.maximum(v[:, 0], 1e-12)) / g + 0.5),
            -np.floor(np.log(np.maximum(v[:, 1], 1e-12)) / g + 0.5),
            -np.floor(v[:, 2] / step + 0.5),
            v[:, 3], v[:, 4], v[:, 5]], axis=1)
    cells = np.where(np.isnan(v), -np.inf, cells)
    ge = (cells[None, :, :] >= cells[:, None, :]).all(axis=-1)  # [i, j]
    gt = (cells[None, :, :] > cells[:, None, :]).any(axis=-1)
    return ~(ge & gt).any(axis=1)

