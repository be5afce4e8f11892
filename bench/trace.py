"""Reduction of a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes one ``.xplane.pb`` per traced run.  Device planes are
named ``/device:TPU:<i>``; on each, the ``XLA Ops`` line holds one event per
executed HLO operation and the ``XLA Modules`` line one per executable run
(named after the jitted function: ``jit_chunk(...)``).  The host plane
``/host:CPU`` holds the benchmark's own spans (``bench.window``,
``bench.call``) on the Python thread, on the same clock.

Everything here is plain arithmetic on (start, end) intervals, so that it can
be tested on a small recorded trace (``bench/tests/data/``).
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Interval = Tuple[float, float]  # seconds on the trace clock

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    window: Interval
    ops: Dict[str, List[Tuple[str, float, float]]]  # device -> (op name, start, end)
    modules: Dict[str, List[Tuple[str, float, float]]]  # device -> (module, start, end)
    host: List[Tuple[str, float, float, int]] = field(default_factory=list)  # (name, start, end, depth)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``) into a :class:`Trace`."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    ops, modules, host = {}, {}, []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name or plane.name.startswith("/device:GPU"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                stack: List[float] = []
                for e in line.events:
                    s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                    while stack and stack[-1] <= s:
                        stack.pop()
                    host.append((e.name, s, t, len(stack)))
                    stack.append(t)
                    if e.name == WINDOW_SPAN:
                        window = (s, t)
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return Trace(window=window, ops=ops, modules=modules, host=host)


def union_length(intervals: List[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, end = 0.0, lo
    for s, t in sorted(intervals):
        s, t = max(s, end), min(t, hi)
        if t > s:
            total += t - s
            end = t
    return total


def busy_s(tr: Trace) -> Dict[str, float]:
    """Per device: seconds of the window in which some operation ran."""
    lo, hi = tr.window
    return {d: union_length([(s, t) for _, s, t in evs], lo, hi) for d, evs in tr.ops.items()}


def _clipped(evs, lo, hi):
    return [(n, max(s, lo), min(t, hi)) for n, s, t in evs if min(t, hi) > max(s, lo)]


def module_s(tr: Trace, pattern: str) -> float:
    """Device seconds of executables whose name matches ``pattern``
    (a regular expression, searched), inside the window, averaged over
    devices."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    per = [sum(t - s for n, s, t in _clipped(evs, lo, hi) if rx.search(n))
           for evs in tr.modules.values()]
    return sum(per) / len(per) if per else 0.0


def op_name(event_name: str) -> str:
    """An HLO op event is named by its whole instruction text; keep the
    instruction's name (``%while.273 = (...) while(...)`` -> ``while.273``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations with the most device time in the window (first
    device), as ``[name, seconds]``.  A loop's time includes its body's."""
    if not tr.ops:
        return []
    lo, hi = tr.window
    acc: Dict[str, float] = {}
    for name, s, t in _clipped(next(iter(sorted(tr.ops.items())))[1], lo, hi):
        acc[op_name(name)] = acc.get(op_name(name), 0.0) + (t - s)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of the first device inside the window,
    each named by the innermost host event that covers its middle, as
    ``[name, seconds]``."""
    if not tr.ops:
        return []
    lo, hi = tr.window
    ivs = sorted((s, t) for _, s, t in next(iter(sorted(tr.ops.items())))[1])
    gaps, end = [], lo
    for s, t in ivs:
        if s > end:
            gaps.append((min(s, hi) - end, end, min(s, hi)))
        end = max(end, t)
        if end >= hi:
            break
    if end < hi:
        gaps.append((hi - end, end, hi))
    out = []
    for g, s, t in sorted(gaps, reverse=True)[:n]:
        mid = (s + t) / 2
        cover = [(d, name) for name, hs, ht, d in tr.host if hs <= mid <= ht]
        out.append([max(cover)[1] if cover else "(no host event)", g])
    return out
