"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, the compile counter, and the result line.

A cell (a ``workloads`` entry of ``BENCHMARK.json``) names a
configuration and a traffic mix.  The harness finds

* the configuration at the ``file`` its ``configs`` entry gives; its
  ``runner`` key names ``bench/runners/<runner>.py``, which runs it;
* the traffic mix at ``bench/traffic/<traffic>.json``;
* every metric at ``bench/metrics/<metric name>.py``, a reader with a
  ``read(run)`` that returns a number, or None where it finds nothing.

So a new configuration, mix or metric is a new file and a new entry.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")          # traces; git-ignored


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict[str, Any]                 # the workloads entry
    config: Dict[str, Any]                # the configuration file
    mix: Dict[str, Any]                   # the traffic file
    metrics: List[Dict[str, Any]]         # metric entries for this cell


@dataclasses.dataclass
class Run:
    """What a runner hands to the metric readers."""

    setup_s: float
    window_s: float
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]      # name -> (value, limit)
    values: Dict[str, Any]                      # raw readings
    memory_peak_bytes: int = 0
    trace: Any = None                           # trace.Reduced or None

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _metric_applies(metric: Dict[str, Any], cell: str,
                    e2e_cells: Dict[str, Optional[List[str]]]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:                     # an end-to-end metric
        return True
    cells = e2e_cells.get(moves)
    return cells is None or cell in cells


def find_cell(name: str, entry: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, or, where ``entry`` is
    given, a cell of that shape that the file does not list (the tools
    and the self-check rehearse a mix before it becomes a cell); such a
    cell reports only the metrics that apply to every cell."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if entry is None:
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg["file"]))
    mix = load_json(os.path.join(BENCH, "traffic",
                                 entry["traffic"] + ".json"))
    e2e_cells = {m["name"]: m.get("workloads")
                 for m in bench["end_to_end"]}
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if _metric_applies(m, name, e2e_cells):
                metrics.append(dict(m, kind=kind))
    return Cell(name=name, entry=entry, config=config, mix=mix,
                metrics=metrics)


def load_module(path: str, tag: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + tag.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(name: str):
    return load_module(os.path.join(BENCH, "runners", name + ".py"),
                       "runner_" + name)


def reader(metric: str):
    return load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                       "metric_" + metric)


def require_devices(n_chips: int):
    """The devices JAX found; :class:`NoDevice` unless they are at least
    ``n_chips`` accelerators."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoDevice("JAX found no accelerator, only the CPU")
    if len(devices) < n_chips:
        raise NoDevice(f"the cell needs {n_chips} chips; JAX found "
                       f"{len(devices)}")
    return devices


def import_program() -> str:
    """Put the program under test on the path and turn on its persistent
    compile cache (the checkout's fixed directory, or
    ``JAX_COMPILATION_CACHE_DIR``).  Every program goes into the cache,
    however fast it compiled, so a cell's later runs compile nothing."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program is not at {src}")
    sys.path.insert(0, src)
    import jax
    import repro

    cache = repro.use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent
    cache, and the cache hits among them, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        from jax._src import dispatch

        self.compiles = 0
        self.cache_hits = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **kw):
            if event == self._event:
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def device_info(devices, memory_peak_bytes: int) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def result_line(cell: Cell, run: Run, device: Dict[str, Any],
                traced: bool) -> Dict[str, Any]:
    """The result line: the cell's end-to-end metrics (untraced
    run) or per-layer metrics (traced run), each from its reader; the
    numbers compared, each beside its limit, come last."""
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m["kind"] != kind:
            continue
        value = reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out: Dict[str, Any] = {
        "correct": run.correct, "attempted": int(run.attempted),
        "failed": int(run.failed), "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        out["device"] = dict(device, busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.trace.device_ops],
            "idle_gaps": [[k, v] for k, v in run.trace.idle_gaps]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
