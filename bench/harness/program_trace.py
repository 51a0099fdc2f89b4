"""The program's own spans and device scopes, read from a profiler trace.

The program marks its host steps with ``jax.profiler.TraceAnnotation``
spans named ``repro.<step>`` (``frontier/score.py``,
``montecarlo/streaming.py``) and its device code with ``jax.named_scope``
names: ``repro.sample``, ``repro.decide``, ``repro.sketch`` and
``repro.merge``.  ``trace.load`` keeps only the benchmark's own
``bench.*`` spans and the device ops by name, so this module reads the
same ``.xplane.pb`` a second time for the rest:

- ``spans``: every host event named ``repro.*``, on the trace's clock;
- ``launches``: the start of each host event that launches one device
  program (the TPU runtime's ``PJRT_LoadedExecutable_Execute``);
- ``ops``: per device, each ``XLA Ops`` event with the innermost
  ``repro.*`` scope of its JAX name stack (``None`` where it has none).
  On the TPU the name stack is the ``tf_op`` stat of the op's event
  metadata (the HLO instruction's ``op_name``), which
  ``jax.profiler.ProfileData`` does not expose; ``_op_stacks`` reads it
  from the protobuf wire format directly.

A trace of a program without these spans reads as empty: every reader
built on it then returns ``None``.

    cd bench && python3 -m harness.program_trace <file.xplane.pb>

prints the per-batch split of one trace: host spans, the programs each
launched, device time by scope, and idle time by the innermost span open
in each gap.
"""
from __future__ import annotations

import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from . import trace as trace_mod

SPAN_PREFIX = "repro."
LAUNCH = "PJRT_LoadedExecutable_Execute"
NAME_STACK_STAT = "tf_op"
SCOPES = ("sample", "decide", "sketch", "merge")
SCOPE = re.compile(r"(?:^|/)repro\.(" + "|".join(SCOPES) + r")(?=[/:]|$)")
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".trace")

Op = Tuple[str, float, float, Optional[str]]   # name, start, end, scope


@dataclass
class ProgramTrace:
    spans: List[trace_mod.Event] = field(default_factory=list)
    launches: List[float] = field(default_factory=list)
    ops: Dict[int, List[Op]] = field(default_factory=dict)


def scope_of(name_stack: Optional[str]) -> Optional[str]:
    """Innermost ``repro.<scope>`` of a JAX name stack, or None."""
    found = SCOPE.findall(name_stack or "")
    return found[-1] if found else None


# -- the XPlane wire format, for what ProfileData leaves out ----------------

def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i
        s += 7


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message; a length-delimited
    value is a ``memoryview`` slice, a fixed-width one is skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _op_stacks(data: bytes) -> Dict[str, str]:
    """Name of each device event metadata -> its ``tf_op`` stat.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and
    stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata: name = 2,
    stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
    str_value = 5, ref_value = 7 (a stat metadata id whose name is the
    string)."""
    out: Dict[str, str] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum in (4, 5) and trace_mod.DEVICE_PLANE.match(name):
                value = next((x for k, x in _fields(v) if k == 2), None)
                if value is None:
                    continue
                if pnum == 4:
                    events.append(value)
                else:
                    md = dict(_fields(value))
                    stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        wanted = {k for k, v in stat_names.items() if v == NAME_STACK_STAT}
        for md in events:
            ev_name, stack = None, None
            for k, v in _fields(md):
                if k == 2:
                    ev_name = bytes(v).decode()
                elif k == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        stack = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7)))
            if ev_name is not None and stack:
                out[ev_name] = stack
    return out


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    stacks = _op_stacks(data)
    pt = ProgramTrace()
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            ops = pt.ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    ops.extend((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                scope_of(stacks.get(e.name)))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        pt.spans.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
                    elif e.name == LAUNCH:
                        pt.launches.append(e.start_ns)
    return pt


def of(tr: trace_mod.Trace) -> ProgramTrace:
    """The program's part of the trace ``tr`` was read from.  A ``Trace``
    carries it as ``tr.program`` once read; otherwise it is read from the
    trace directory ``bench/run.py`` writes, which holds that one trace."""
    pt = getattr(tr, "program", None)
    if pt is None:
        pt = load(trace_mod.find_xplane(TRACE_DIR))
        tr.program = pt
    return pt


def load_both(path: str) -> trace_mod.Trace:
    """``trace.load(path)`` with the program's part attached."""
    tr = trace_mod.load(path)
    tr.program = load(path)
    return tr


# -- sums the readers take ---------------------------------------------------

def _clip(s: float, e: float, w) -> float:
    return max(0.0, min(e, w[1]) - max(s, w[0]))


def span_ms_per_batch(tr: trace_mod.Trace, name: str) -> Optional[float]:
    """Milliseconds per batch inside host spans called ``name``."""
    spans = [s for s in of(tr).spans if s[0] == name]
    w = tr.window
    if not spans or w is None or not tr.n_batches:
        return None
    return 1e-6 * sum(_clip(s, e, w) for _, s, e in spans) / tr.n_batches


def launches_in(tr: trace_mod.Trace, name: str) -> Optional[float]:
    """Device programs launched from inside host spans called ``name``, per
    batch.  The host's launch events are counted, not the device's module
    starts: a program queued before the span can start on the device
    inside it."""
    pt = of(tr)
    w = tr.window
    spans = [(s, e) for n, s, e in pt.spans
             if n == name and e > w[0] and s < w[1]] if w else []
    if not spans or not pt.launches or not tr.n_batches:
        return None
    n = sum(1 for t in pt.launches if any(s <= t < e for s, e in spans))
    return n / tr.n_batches


def scope_ms_per_batch(tr: trace_mod.Trace, scope: str) -> Optional[float]:
    """Device milliseconds per batch of ops under ``repro.<scope>``, averaged
    over devices; loops and calls are left out (their body's ops count)."""
    pt = of(tr)
    w = tr.window
    devs = tr.devices
    if w is None or not devs or not tr.n_batches:
        return None
    tot = sum(_clip(s, e, w) for d in devs for name, s, e, sc in
              pt.ops.get(d, ()) if sc == scope
              and not trace_mod.CONTAINER_OP.search(name))
    return 1e-6 * tot / len(devs) / tr.n_batches if tot > 0 else None


def top_gaps(tr: trace_mod.Trace, k: int = 10) -> List[List]:
    """``Trace.top_gaps`` with the program's spans beside the benchmark's:
    idle seconds by the innermost span of either set, averaged over
    devices."""
    both = trace_mod.Trace(ops=tr.ops, modules=tr.modules,
                           spans=tr.spans + of(tr).spans)
    return both.top_gaps(k)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 -m harness.program_trace <file.xplane.pb>",
              file=sys.stderr)
        return 2
    tr = load_both(args[0])
    nb = tr.n_batches
    print(f"window {tr.window_s:.6f} s, {nb} batch(es), "
          f"busy {tr.mean_busy_s():.6f} s")
    for name in sorted({s[0] for s in tr.program.spans}):
        print(f"span {name}: {span_ms_per_batch(tr, name)} ms/batch, "
              f"{launches_in(tr, name)} programs launched/batch")
    for sc in SCOPES:
        print(f"scope repro.{sc}: {scope_ms_per_batch(tr, sc)} device "
              f"ms/batch")
    for name, v in top_gaps(tr):
        print(f"idle {name}: {v / max(nb, 1) * 1e3:.3f} ms/batch")
    return 0


if __name__ == "__main__":
    sys.exit(main())
