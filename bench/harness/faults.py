"""Faults planted in the timed path, to see ``correct`` come out false.

Each is a context manager that patches the program in memory for its
duration (no file changes) and clears JAX's caches on entry and exit, so
the patched code is traced afresh and nothing patched outlives it.
"""
from __future__ import annotations

import contextlib
from dataclasses import replace

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, value):
    import jax
    old = getattr(obj, name)
    setattr(obj, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, old)
        jax.clear_caches()


def state_unchanged():
    """Every chunk step returns the summary it was given."""
    from repro.montecarlo.streaming import StreamSummary
    return _patched(StreamSummary, "_absorb", lambda self, **_: self)


def half_batch():
    """Both passes stream half the batch's trials; every rate and
    quantile is then taken over the half that ran."""
    from repro.frontier import score as fscore
    real = fscore.streaming

    class Half:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def race_stream(*a, trials, **kw):
            return real.race_stream(*a, trials=trials // 2, **kw)

        @staticmethod
        def fast_path_stream(*a, trials, **kw):
            return real.fast_path_stream(*a, trials=trials // 2, **kw)
    return _patched(fscore, "streaming", Half())


@contextlib.contextmanager
def no_exchange():
    """The cross-chip merge is skipped: each chip keeps its own counts and
    the result is read from the first chip (``shard_map`` is told not to
    check that its output is replicated, or it would refuse to trace)."""
    import functools

    import jax
    from repro.montecarlo.streaming import StreamSummary
    unchecked = functools.partial(jax.shard_map, check_vma=False)
    with _patched(StreamSummary, "axis_merge", lambda self, axis: self), \
            _patched(jax, "shard_map", unchecked):
        yield


def answer_mask():
    """The frontier mask comes out with its first system flipped."""
    from repro.frontier import score as fscore
    real = fscore.pareto_mask

    def flipped(values, axes):
        m = np.array(real(values, axes), bool)
        m[0] = ~m[0]
        return m
    return _patched(fscore, "pareto_mask", flipped)


def answer_quantile():
    """Every sketch quantile comes out 3 sketch precisions high."""
    from repro.montecarlo.streaming import StreamSummary
    real = StreamSummary.quantile

    def shifted(self, q):
        return real(self, q) * (1.0 + 3.0 * self.precision)
    return _patched(StreamSummary, "quantile", shifted)


def answer_count():
    """One system's recovery count comes out one higher."""
    from repro.montecarlo.streaming import StreamSummary
    real = StreamSummary._absorb

    def bumped(self, **kw):
        out = real(self, **kw)
        return replace(out, n_recovery=out.n_recovery.at[0].add(
            (kw["n_recovery"] > 0)[0].astype(out.n_recovery.dtype)))
    return _patched(StreamSummary, "_absorb", bumped)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "answer_mask": answer_mask,
          "answer_quantile": answer_quantile, "answer_count": answer_count}
