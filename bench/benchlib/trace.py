"""Reduction of a JAX profiler trace to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``), and reduces it to:

* ``busy_s`` -- the union of the intervals in which an operation ran on
  a device, inside the traced window, averaged over the devices;
* ``window_s`` -- the length of the traced window: the host span
  ``bench.window`` that the harness opens around the measured rounds;
* ``modules`` -- per compiled program (XLA module) name: executions and
  device seconds inside the window;
* ``device_ops`` -- device seconds per operation name;
* ``idle_gaps`` -- device-idle seconds inside the window, by the
  innermost ``bench.*`` host span that covers each gap's midpoint (what
  the host was doing while the device waited).

On a TPU the device planes are ``/device:TPU:<n>`` with the lines
``XLA Ops`` and ``XLA Modules``; the names are parameters so that the
self-check can read a trace recorded on the CPU.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    modules: Dict[str, Tuple[int, float]]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def options():
    """Profiler options of a traced run: device activity and the host's
    annotations (``TraceAnnotation``), without the Python call tracer,
    which would time every Python call of the host loop."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def union_ns(intervals: np.ndarray) -> np.ndarray:
    """Merge (K, 2) [start, end) intervals into disjoint sorted ones."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, dtype=float)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    c = np.clip(intervals, lo, hi)
    return c[c[:, 1] > c[:, 0]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path: str, *, device_prefix: str, op_line: str,
                module_line: str, span_prefix: str):
    """Device ops and modules per device plane, and the host spans whose
    names start with ``span_prefix``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        is_device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            if is_device and line.name.startswith(op_line):
                dest = ops.setdefault(plane.name, [])
            elif is_device and line.name.startswith(module_line):
                dest = modules.setdefault(plane.name, [])
            else:
                dest = None
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if dest is not None:
                    if dur > 0:
                        dest.append((start, start + dur, ev.name))
                elif ev.name.startswith(span_prefix):
                    spans.append((start, start + dur, ev.name))
    return ops, modules, spans


def reduce_events(ops: Dict[str, List[Interval]],
                  modules: Dict[str, List[Interval]],
                  spans: List[Interval], *,
                  window_span: str = "bench.window", top: int = 10
                  ) -> Optional[Reduced]:
    """The device metrics of the traced window, or None where no
    operation ran on a device inside it."""
    win = [s for s in spans if s[2] == window_span]
    if not win or not ops:
        return None
    lo, hi = win[0][0], win[0][1]
    window_ns = hi - lo
    busy, op_time = [], {}
    merged_all = []
    for plane, evs in ops.items():
        iv = clip(np.asarray([(a, b) for a, b, _ in evs], float), lo, hi)
        merged = union_ns(iv)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        merged_all.append(merged)
        for a, b, name in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
    if sum(busy) <= 0:
        return None
    mods: Dict[str, Tuple[int, float]] = {}
    for evs in modules.values():
        for a, b, name in evs:
            if a >= lo and b <= hi:
                n, t = mods.get(name, (0, 0.0))
                mods[name] = (n + 1, t + (b - a) * 1e-9)
    # idle gaps of the first device, by the host span that covers each
    # gap's midpoint (the harness's spans inside the window do not nest)
    merged = merged_all[0]
    edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
    edges = edges[edges[:, 1] > edges[:, 0]]
    inner = sorted((s for s in spans if s[2] != window_span),
                   key=lambda s: s[0])
    starts = np.asarray([s[0] for s in inner], float)
    ends = np.asarray([s[1] for s in inner], float)
    mids = edges.mean(axis=1)
    at = np.searchsorted(starts, mids, side="right") - 1
    gaps: Dict[str, float] = {}
    for (a, b), i, mid in zip(edges, at, mids):
        name = inner[i][2] if i >= 0 and ends[i] >= mid else window_span
        gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    return Reduced(
        window_s=window_ns * 1e-9,
        busy_s=float(np.mean(busy)) * 1e-9,
        n_devices=len(busy),
        modules=mods,
        device_ops=sorted(((k, v * 1e-9) for k, v in op_time.items()),
                          key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:top])


def reduce_trace(trace_dir: str, *, device_prefix: str = "/device:TPU:",
                 op_line: str = "XLA Ops",
                 module_line: str = "XLA Modules",
                 span_prefix: str = "bench.") -> Optional[Reduced]:
    ops, modules, spans = read_events(
        find_xplane(trace_dir), device_prefix=device_prefix,
        op_line=op_line, module_line=module_line, span_prefix=span_prefix)
    return reduce_events(ops, modules, spans)
