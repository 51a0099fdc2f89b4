"""Warm compiled-engine pool for planner queries (DESIGN.md §11).

``montecarlo.streaming._stream`` compiles once per engine geometry (table
shapes, chunking, precision, resolved saturation depths, mesh — all
decided in ``streaming``), and JAX's jit cache keeps that compile warm
for the life of the process.  What a long-lived planner needs on top is
bookkeeping and memoization: ``EngineCache`` routes scoring queries
through ``frontier.score`` while counting the compiles each actually paid
(the ``engine.TRACE_COUNTS`` delta around the call), and keeps an LRU of
full ``FrontierResult``s keyed by a *content* fingerprint (table bytes +
delay leaves + the stream settings + every other parameter), so a
bit-identical repeat query returns without running the engine at all.

The planner service keeps one ``EngineCache`` for its whole lifetime; the
successive-halving search threads one through all its rungs.  The
"second same-shape query adds zero compiles" acceptance criterion is
asserted against ``TRACE_COUNTS`` in tests/test_planner.py and the CI
planner smoke job.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import replace
from typing import Dict, Optional, Sequence

import numpy as np

import jax

from repro.frontier import score as fscore
from repro.montecarlo import engine, streaming
from repro.montecarlo.regimes import MarkovRegimes

# The planner scores on one device unless a query asks for the trial mesh.
DEFAULT_STREAM = replace(fscore.DEFAULT_STREAM, shard=False)

# The base per-path trace counters.  The ``*_sortfree`` / ``*_fused`` keys
# increment ALONGSIDE their base key (they pin which lowering ran), so
# summing everything would double-count one trace.
BASE_TRACE_KEYS = ("race", "fast_path", "classic_path",
                   "race_stream", "fast_path_stream", "classic_path_stream")


def trace_total() -> int:
    """Total engine traces so far (jit cache misses across all paths)."""
    return sum(engine.TRACE_COUNTS[k] for k in BASE_TRACE_KEYS)


def _delay_token(delay) -> bytes:
    """Content fingerprint of a delay-model pytree (class + leaf bytes)."""
    if delay is None:
        return b"default"
    leaves, treedef = jax.tree_util.tree_flatten(delay)
    h = hashlib.sha256(str(treedef).encode())
    h.update(type(delay).__name__.encode())
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.digest()


class EngineCache:
    """Warm engine pool + result memo for a long-lived planner process.

    ``score`` has the semantics of ``frontier.score.score_systems`` (same
    ``FrontierResult``, bit-identical values; the stream settings come as
    one ``stream``) with two additions: an ``engine_compiles`` attribute
    on the returned result (the TRACE_COUNTS delta this call caused), and
    an LRU memo of results so a bit-identical repeat query skips the
    engine entirely (memo hits report ``engine_compiles == 0`` without
    even entering jit dispatch).
    """

    def __init__(self, memo_size: int = 64):
        self.memo_size = memo_size
        self._memo: "OrderedDict[bytes, object]" = OrderedDict()
        self.engine_compiles = 0
        self.memo_hits = 0
        self.memo_misses = 0

    def stats_dict(self) -> Dict[str, float]:
        return {"engine_compiles": float(self.engine_compiles),
                "memo_hits": float(self.memo_hits),
                "memo_misses": float(self.memo_misses)}

    # -- the one entry point ----------------------------------------------
    def score(self, systems: Sequence, *, trials: int,
              n: Optional[int] = None, k_proposers: int = 2,
              delta_ms: float = fscore.DEFAULT_DELTA_MS, delay=None,
              stream: streaming.StreamSettings = DEFAULT_STREAM,
              seed: int = 0, regimes=None, recovery: str = "coordinated",
              axes=None):
        systems = list(systems)
        with jax.profiler.TraceAnnotation("repro.score", systems=len(systems),
                                          trials=trials, seed=seed):
            low = fscore._lower(systems, n)
            if isinstance(regimes, dict):    # serialized chain: resolve once
                regimes = MarkovRegimes.from_config(regimes, low.n)
            fp = _fingerprint(low, stream=stream, k_proposers=k_proposers,
                              trials=trials, seed=seed, delta_ms=delta_ms,
                              delay=delay, regimes=regimes,
                              recovery=recovery, axes=axes)
            hit = self._memo.get(fp)
            if hit is not None:
                self._memo.move_to_end(fp)
                self.memo_hits += 1
                out = replace(hit)              # fresh wrapper, shared arrays
                out.engine_compiles = 0
                return out
            self.memo_misses += 1

            before = trace_total()
            result = fscore._score(low, trials=trials,
                                   k_proposers=k_proposers,
                                   delta_ms=delta_ms, delay=delay,
                                   stream=stream, seed=seed, regimes=regimes,
                                   recovery=recovery, axes=axes)
            result.engine_compiles = trace_total() - before
            self.engine_compiles += result.engine_compiles

            self._memo[fp] = result
            while len(self._memo) > self.memo_size:
                self._memo.popitem(last=False)
            return result


def _fingerprint(low, *, stream: streaming.StreamSettings, k_proposers: int,
                 trials: int, seed: int, delta_ms: float, delay, regimes,
                 recovery: str, axes) -> bytes:
    """Content hash of one scoring query: the table, the labels, the
    stream settings and the devices ``shard`` spans, the delay and regime
    models by their leaves, and every other parameter."""
    h = hashlib.sha256(repr((low.n, low.labels, stream, stream.devices(),
                             k_proposers, trials, seed, delta_ms,
                             recovery)).encode())
    for name in sorted(low.table):
        arr = np.asarray(low.table[name])
        h.update(name.encode())
        h.update(repr((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    h.update(_delay_token(delay))
    h.update(_delay_token(regimes))         # content token works per-pytree
    h.update(repr(tuple(axes) if axes is not None else None).encode())
    return h.digest()
