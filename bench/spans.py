"""The program's own spans and scopes in a profiler trace.

``run_simulation`` and ``fleet_program`` open host spans on the Python thread
and name the epoch body's phases with ``jax.named_scope``:

* host spans ``ehfl.init_carry``, ``ehfl.chunk`` (one ``eval_every`` chunk's
  dispatch), ``ehfl.trace_chunk`` (inside a chunk span whose dispatch traced
  the epoch program again) and ``ehfl.eval`` (the eval round trip);
* device scopes ``ehfl.vaoi_proxy``, ``ehfl.slot_scan``, ``ehfl.local_train``,
  ``ehfl.eq6_moment`` (inside ``ehfl.local_train``) and ``ehfl.fedavg``, as
  segments of each HLO instruction's ``op_name`` metadata (the ``tf_op`` a
  device trace shows).

A trace event of a device op names its instruction but not its metadata, so
the scopes are read from the compiled epoch program itself: the cell's
jitted chunk is compiled again after the window, with the metadata in the
compilation cache's key, so that an executable cached from a program with
other scopes (JAX keys the cache without metadata by default) cannot stand
in for it.  Its instruction names are those of the executable that ran; a
trace op the compiled chunk lacks raises.

A trace of a program without the spans reads as empty here.
"""
from __future__ import annotations

import re
from bisect import bisect_right
from operator import itemgetter
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace as tr

INIT_SPAN = "ehfl.init_carry"
CHUNK_SPAN = "ehfl.chunk"
TRACE_CHUNK_SPAN = "ehfl.trace_chunk"
EVAL_SPAN = "ehfl.eval"
CHUNK_TRACE_EVENT = "/ehfl/drivers/chunk_trace"
SCOPE_PREFIX = "ehfl."

# where the device's idle time goes (the buckets of :func:`idle_by_span`)
BUCKETS = ("init_carry", "chunk_traced", "chunk_untraced", "eval", "unattributed")

Ops = Dict[str, List[Tuple[str, float, float]]]  # device -> (instruction, start, end)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')


def has_spans(trace: tr.Trace) -> bool:
    """Whether the traced program opens the host spans above."""
    return any(name == CHUNK_SPAN for name, _, _, _ in trace.host)


def hlo_op_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` metadata (the JAX name stack,
    ``jit(chunk)/while/body/ehfl.local_train/...``), for every instruction
    of a compiled module's text; ``""`` where it has none."""
    out = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        out[name] = m.group(1) if m else ""
    return out


def module_ops(trace: tr.Trace, pattern: str) -> Ops:
    """Per device, the ops (instruction name, start, end) that ran inside a
    run of an executable whose name matches ``pattern`` (searched)."""
    rx = re.compile(pattern)
    out: Ops = {}
    for dev, evs in trace.ops.items():
        runs = sorted((s, t) for name, s, t in trace.modules.get(dev, ()) if rx.search(name))
        starts = [s for s, _ in runs]
        names: Dict[str, str] = {}  # millions of events, a few thousand distinct texts
        kept = []
        for text, s, t in evs:
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                if text not in names:
                    names[text] = tr.op_name(text)
                kept.append((names[text], s, t))
        out[dev] = kept
    return out


def _scoped(paths: Dict[str, str], scopes: Iterable[str]) -> set:
    want = set(scopes)
    return {name for name, path in paths.items() if want.intersection(path.split("/"))}


def scope_s(ops: Ops, paths: Dict[str, str], scopes: Iterable[str], window: tr.Interval) -> float:
    """Device seconds inside ``window`` of the ``ops`` whose instruction's
    path has one of ``scopes`` as a ``/``-separated segment, averaged over
    devices.  A union of intervals: a loop op and its scoped body count
    once."""
    hit = _scoped(paths, scopes)
    lo, hi = window
    per = [tr.union_length([(s, t) for name, s, t in evs if name in hit], lo, hi)
           for evs in ops.values()]
    return sum(per) / len(per) if per else 0.0


def chunk_op_paths(cell) -> Dict[str, str]:
    """:func:`hlo_op_paths` of the cell's jitted chunk (the traffic's
    ``modules.epoch`` executable), compiled for the default device from
    abstract arguments of the shapes a call gives it (the backend, which
    holds any frozen weights of the family, made from seed 0)."""
    import jax

    from bench import harness
    from repro.core import simulator

    if cell.traffic["entry"] != "run_simulation":
        raise ValueError(f"unsupported entry {cell.traffic['entry']!r}")
    horizon = cell.traffic["horizon"]
    cfg = harness.sim_config(cell, 0, horizon)
    every = max(1, cfg.eval_every)
    if horizon > every and horizon % every:
        raise ValueError("a call of two chunk lengths runs two chunk executables")
    be = harness.backend(cell, harness.make_frozen(cell, 0))
    fn = simulator.chunk_program(cfg, be, bool(cell.traffic.get("use_kernel", False)))
    carry = jax.eval_shape(lambda: simulator.init_carry(cfg, be))
    data = jax.eval_shape(lambda: cell.family.make_dataset(0, cell.config["data"],
                                                           cell.config["model"]))
    ts = jax.eval_shape(lambda: jax.numpy.arange(min(every, horizon)))
    key = "jax_compilation_cache_include_metadata_in_key"
    saved = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        text = fn.lower(carry, ts, data["images"], data["labels"]).compile().as_text()
    finally:
        jax.config.update(key, saved)
    return hlo_op_paths(text)


def chunk_scope_s(ctx, scopes: Iterable[str]) -> Optional[float]:
    """Device seconds of the traced window's chunk ops under ``scopes``
    (:func:`scope_s` with the paths of :func:`chunk_op_paths`).  ``None``
    where the trace holds no device op or no program span, or where the
    compiled chunk has none of ``scopes``; raises where the program opens
    its spans but the compiled chunk names no phase, or where the trace ran
    a chunk op the compiled chunk lacks.  The compile is made once per run
    and kept on ``ctx`` for the other scope readers."""
    if ctx.trace is None or not ctx.trace.ops or not has_spans(ctx.trace):
        return None
    if getattr(ctx, "chunk_scopes", None) is None:
        paths = chunk_op_paths(ctx.cell)
        if not any(seg.startswith(SCOPE_PREFIX) for p in paths.values() for seg in p.split("/")):
            raise ValueError("the program opens its spans, but its compiled chunk names no "
                             f"{SCOPE_PREFIX}* scope")
        ops = module_ops(ctx.trace, ctx.cell.traffic["modules"]["epoch"])
        if not any(ops.values()):
            raise ValueError("the trace holds no op of the chunk executable")
        missing = {name for evs in ops.values() for name, _, _ in evs} - paths.keys()
        if missing:
            raise ValueError("the traced chunk ran ops its compiled program lacks: "
                             f"{sorted(missing)[:5]}")
        ctx.chunk_scopes = (ops, paths)
    ops, paths = ctx.chunk_scopes
    if not _scoped(paths, scopes):
        return None
    return scope_s(ops, paths, scopes, ctx.trace.window)


def _idle(evs: List[Tuple[str, float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which no op of ``evs`` ran."""
    out, end = [], lo
    for _, s, t in sorted(evs, key=itemgetter(1)):
        if s > end:
            out.append((end, min(s, hi)))
        if t > end:
            end = t
            if end >= hi:
                break
    if end < hi:
        out.append((end, hi))
    return [(s, t) for s, t in out if t > s]


def _labelled(trace: tr.Trace) -> List[Tuple[float, float, str]]:
    """Consecutive segments of the time axis, each labelled with the bucket
    of the innermost program span covering it (segments no span covers are
    left out)."""
    traced = [(s, t) for name, s, t, _ in trace.host if name == TRACE_CHUNK_SPAN]
    spans = []
    for name, s, t, depth in trace.host:
        if name == INIT_SPAN:
            label = "init_carry"
        elif name == EVAL_SPAN:
            label = "eval"
        elif name == CHUNK_SPAN:
            inner = any(s <= a and b <= t for a, b in traced)
            label = "chunk_traced" if inner else "chunk_untraced"
        else:
            continue
        spans.append((s, t, depth, label))
    cuts = sorted({x for s, t, _, _ in spans for x in (s, t)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [(depth, s, label) for s, t, depth, label in spans if s <= a and b <= t]
        if cover:
            out.append((a, b, max(cover)[2]))
    return out


def idle_by_span(trace: tr.Trace) -> Dict[str, float]:
    """Seconds of the window in which the device was idle, split by overlap
    among the innermost program span covering each instant (``BUCKETS``),
    averaged over devices.  A chunk span that holds a ``ehfl.trace_chunk``
    span is ``chunk_traced``.  The buckets sum to the window's idle time,
    ``window_s - mean busy_s``."""
    out = dict.fromkeys(BUCKETS, 0.0)
    if not trace.ops:
        return out
    lo, hi = trace.window
    segments = _labelled(trace)
    for evs in trace.ops.values():
        j = 0
        for s, t in _idle(evs, lo, hi):
            covered = 0.0
            while j < len(segments) and segments[j][1] <= s:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < t:
                a, b, label = segments[k]
                part = min(b, t) - max(a, s)
                if part > 0:
                    out[label] += part
                    covered += part
                k += 1
            out["unattributed"] += (t - s) - covered
    n = len(trace.ops)
    return {k: v / n for k, v in out.items()}
