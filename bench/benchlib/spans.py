"""Host spans of a JAX profiler trace: nesting, self time, and what the
host was doing in each part of the device's idle time.

The program opens ``jax.profiler.TraceAnnotation`` spans inside
``GroupStream.step`` (``spindle.stream.step``, and nested in it
``spindle.stream.dispatch`` and ``spindle.stream.readback``); the
harness opens ``bench.*`` spans around its calls.  So spans nest: a
span's self time is its duration less the spans nested in it on the
same thread, and an idle gap is split by the innermost span over each
part of it, where :func:`benchlib.trace.reduce_events` gives a whole gap
to the one span under its midpoint.

A span is ``(start_ns, end_ns, name, thread)``; the thread is the
(plane, line) of the trace that recorded it.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import numpy as np

Span = Tuple[float, float, str, object]
Piece = Tuple[float, float, str]


class SpanStats(NamedTuple):
    count: int
    total_s: float
    self_s: float
    max_s: float


def read_spans(path: str, prefix=("bench.", "spindle.")) -> List[Span]:
    """Every span of the ``.xplane.pb`` at ``path`` whose name starts
    with ``prefix`` (a string or a tuple of them), on any plane."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(prefix):
                    start = float(ev.start_ns)
                    out.append((start, start + float(ev.duration_ns),
                                ev.name, (plane.name, k)))
    return out


def self_times(spans: List[Span]) -> List[Tuple[Span, float]]:
    """Each span with its self time in ns: its duration less the spans
    nested in it on the same thread."""
    out = []
    threads: Dict[object, List[Span]] = {}
    for sp in spans:
        threads.setdefault(sp[3], []).append(sp)
    for group in threads.values():
        group.sort(key=lambda sp: (sp[0], -sp[1]))
        stack: List[list] = []                  # [span, self_ns]
        for sp in group:
            while stack and stack[-1][0][1] <= sp[0]:
                out.append(tuple(stack.pop()))
            if stack:
                parent = stack[-1]
                parent[1] -= min(sp[1], parent[0][1]) - sp[0]
            stack.append([sp, sp[1] - sp[0]])
        out.extend(tuple(e) for e in reversed(stack))
    return out


def table(spans: List[Span], lo: float, hi: float) -> Dict[str, SpanStats]:
    """Per span name, over the spans that lie inside [lo, hi]: count,
    total seconds, self seconds and the longest span in seconds."""
    stats: Dict[str, SpanStats] = {}
    inside = [sp for sp in spans if sp[0] >= lo and sp[1] <= hi]
    for sp, own in self_times(inside):
        n, total, self_s, longest = stats.get(sp[2], (0, 0.0, 0.0, 0.0))
        d = (sp[1] - sp[0]) * 1e-9
        stats[sp[2]] = SpanStats(n + 1, total + d, self_s + own * 1e-9,
                                 max(longest, d))
    return stats


def innermost(spans: List[Span], lo: float, hi: float, outer: str
              ) -> List[Piece]:
    """[lo, hi] cut into contiguous pieces, each named by the innermost
    of the (one thread's, nested) spans that covers it, or ``outer``
    where none does."""
    pieces: List[Piece] = []
    stack: List[Tuple[float, str]] = []     # (end, name), innermost last
    t = lo

    def cover(until):
        nonlocal t
        if until > t:
            pieces.append((t, until, stack[-1][1] if stack else outer))
            t = until

    clipped = sorted(((max(sp[0], lo), min(sp[1], hi), sp[2])
                      for sp in spans), key=lambda sp: (sp[0], -sp[1]))
    for a, b, name in clipped:
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            cover(stack[-1][0])
            stack.pop()
        cover(a)
        stack.append((min(b, stack[-1][0]) if stack else b, name))
    while stack:
        cover(stack[-1][0])
        stack.pop()
    cover(hi)
    return pieces


def split_gaps(gaps: np.ndarray, pieces: List[Piece]) -> Dict[str, float]:
    """Seconds of the sorted, disjoint (K, 2) ``gaps`` under each piece's
    name; ``pieces`` (from :func:`innermost`) cover every gap."""
    out: Dict[str, float] = {}
    i = 0
    for a, b in gaps:
        while pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            pa, pb, name = pieces[j]
            out[name] = out.get(name, 0.0) + (min(b, pb) - max(a, pa)) * 1e-9
            j += 1
    return out


def idle_gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The complement in [lo, hi] of the sorted, disjoint (K, 2) busy
    intervals (``benchlib.trace.union_ns`` of the device's ops)."""
    edges = np.concatenate([[lo], np.asarray(busy, float).ravel(), [hi]])
    edges = edges.reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def outside_ns(starts: np.ndarray, spans: List[Span]) -> np.ndarray:
    """For each of ``starts`` (ns), how far it lies outside the nearest
    of ``spans`` (disjoint, one thread): 0 inside one, negative before
    the next span starts, positive after the last one ended."""
    spans = sorted(spans)
    lo = np.asarray([sp[0] for sp in spans], float)
    hi = np.asarray([sp[1] for sp in spans], float)
    starts = np.asarray(starts, float)
    at = np.searchsorted(lo, starts, side="right") - 1
    after = np.where(at >= 0, starts - hi[np.maximum(at, 0)], np.inf)
    nxt = np.minimum(at + 1, len(lo) - 1)
    before = np.where(at + 1 < len(lo), lo[nxt] - starts, np.inf)
    return np.where(np.minimum(after, before) <= 0, 0.0,
                    np.where(before < after, -before, after))
