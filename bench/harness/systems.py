"""The benchmark's own enumeration of a deployment's quorum systems.

A configuration names families and parameters as data; this module turns
them into one ordered list of ``SystemDef`` records.  Each record is
lowered two ways that share nothing: ``to_program`` builds it through the
program's public constructors (what the timed path scores), and
``reference_rows`` / ``reference_fault_tolerance`` describe the same
system as weighted quorum rows for the plain reference.  A change to the
program's own family generators therefore cannot change what is scored.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

PHASES = ("p1", "p2c", "p2f")


@dataclass(frozen=True)
class SystemDef:
    """``kind`` is card | relaxed | grid | weighted; ``params`` holds
    (q1, q2c, q2f), the grid's column count, or (weights, (t1, t2c, t2f))."""

    kind: str
    params: tuple
    label: str


def enumerate_systems(cfg: Dict) -> List[SystemDef]:
    """Every system the configuration names, in a fixed order, checked
    against ``expected_systems``."""
    n = int(cfg["n"])
    out: List[SystemDef] = []
    triples = [(a, b, c) for a in range(1, n + 1) for b in range(1, n + 1)
               for c in range(1, n + 1)]
    for fam in cfg["families"]:
        if fam == "ffp":        # Eqs. 13 and 14
            out += [SystemDef("card", t, "card[%d,%d,%d]" % t)
                    for t in triples
                    if t[0] + t[1] > n and t[0] + 2 * t[2] > 2 * n]
        elif fam == "relaxed":  # Eq. 14 alone, minus what FFP admits
            out += [SystemDef("relaxed", t, "relaxed[%d,%d,%d]" % t)
                    for t in triples
                    if t[0] + 2 * t[2] > 2 * n and not t[0] + t[1] > n]
        elif fam == "grid":
            out += [SystemDef("grid", (int(c),), f"grid.3x{int(c)}")
                    for c in cfg["grid_columns"]]
        elif fam == "weighted":
            for i, w in enumerate(cfg["weighted"]):
                ws, ts = tuple(int(x) for x in w["weights"]), tuple(
                    int(x) for x in w["t"])
                out.append(SystemDef("weighted", (ws, ts),
                                     f"weighted.{i}[t={ts}]"))
        else:
            raise ValueError(f"unknown family {fam!r} in configuration "
                             f"{cfg['name']!r}")
    if len(out) != int(cfg["expected_systems"]):
        raise ValueError(f"configuration {cfg['name']!r} enumerates "
                         f"{len(out)} systems, expected "
                         f"{cfg['expected_systems']}")
    cards = {s.params for s in out if s.kind == "card"}
    for t in cfg.get("must_include", []):
        if tuple(t) not in cards:
            raise ValueError(f"{tuple(t)} missing from {cfg['name']!r}")
    return out


def to_program(defs: Sequence[SystemDef], n: int) -> list:
    """The same systems as the program's own objects, built through its
    public constructors and labeled with the benchmark's labels."""
    from repro.core.quorum import (ExplicitQuorumSystem, QuorumSpec,
                                   RelaxedQuorumSpec, WeightedQuorumSystem)
    from repro.frontier.families import Member

    out = []
    for s in defs:
        if s.kind == "card":
            obj = QuorumSpec(n, *s.params).validate()
        elif s.kind == "relaxed":
            obj = RelaxedQuorumSpec(n, *s.params).validate()
        elif s.kind == "grid":
            obj = ExplicitQuorumSystem.grid(s.params[0]).validate()
        else:
            ws, ts = s.params
            obj = WeightedQuorumSystem(ws, *ts).validate()
        out.append(Member(s.label, obj))
    return out


def _rows(s: SystemDef, n: int) -> Dict[str, List[Tuple[np.ndarray, int]]]:
    """Per phase, a list of (weights over n acceptors, threshold) rows: a
    set of acceptors is a quorum of the phase when it meets some row."""
    def card(q):
        return [(np.ones(n, np.int32), q)]

    if s.kind in ("card", "relaxed"):
        q1, q2c, q2f = s.params
        return {"p1": card(q1), "p2c": card(q2c), "p2f": card(q2f)}
    if s.kind == "weighted":
        ws, (t1, t2c, t2f) = s.params
        w = np.zeros(n, np.int32)
        w[:len(ws)] = ws
        return {"p1": [(w, t1)], "p2c": [(w, t2c)], "p2f": [(w, t2f)]}
    # 3 x C grid, acceptor r*C + c; acceptors past 3C belong to no quorum.
    cols = s.params[0]

    def members(ids):
        w = np.zeros(n, np.int32)
        w[list(ids)] = 1
        return (w, len(ids))

    row = lambda r: {r * cols + c for c in range(cols)}
    col = lambda c: {r * cols + c for r in range(3)}
    return {"p1": [members(row(r) | col(c)) for r in range(3)
                   for c in range(cols)],
            "p2c": [members(col(c)) for c in range(cols)],
            "p2f": [members(row(a) | row(b)) for a in range(3)
                    for b in range(a + 1, 3)]}


def reference_rows(defs: Sequence[SystemDef], n: int
                   ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Per phase, ``(w (M, G, n) int32, t (M, G) float32)`` with unused rows
    padded by an unreachable threshold (inf)."""
    rows = [_rows(s, n) for s in defs]
    out = {}
    for ph in PHASES:
        g = max(len(r[ph]) for r in rows)
        w = np.zeros((len(defs), g, n), np.int32)
        t = np.full((len(defs), g), np.inf, np.float32)
        for m, r in enumerate(rows):
            for j, (wr, tr) in enumerate(r[ph]):
                w[m, j], t[m, j] = wr, tr
        out[ph] = (w, t)
    return out


def _crash_budget(rows: List[Tuple[np.ndarray, int]], n: int) -> int:
    """Largest f such that every set of f crashed acceptors leaves some
    row's live weight at its threshold."""
    f = 0
    while f < n:
        for crash in itertools.combinations(range(n), f + 1):
            alive = np.ones(n, np.int64)
            alive[list(crash)] = 0
            if not any(int(w @ alive) >= t for w, t in rows):
                return f
        f += 1
    return f


def reference_fault_tolerance(defs: Sequence[SystemDef], n: int
                              ) -> np.ndarray:
    """(M, 3) crash budgets (fast, phase1, classic), the frontier's three
    maximized axes.  A Relaxed system must always be able to form its
    full phase-1 quorum max(q1, n + 1 - q2c), so that is what it prices."""
    out = np.zeros((len(defs), 3), np.float64)
    for m, s in enumerate(defs):
        if s.kind in ("card", "relaxed"):
            q1, q2c, q2f = s.params
            q1_need = max(q1, n + 1 - q2c) if s.kind == "relaxed" else q1
            out[m] = (n - q2f, n - q1_need, n - q2c)
        else:
            r = _rows(s, n)
            out[m] = (_crash_budget(r["p2f"], n), _crash_budget(r["p1"], n),
                      _crash_budget(r["p2c"], n))
    return out
