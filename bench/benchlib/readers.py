"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Each reader takes the :class:`benchlib.harness.Run` of one run and
returns a number, or None where the run holds nothing to read (then the
metric is left out of the result line; a share is never reported as 0
for want of a reading).
"""

from __future__ import annotations

from typing import Optional

from benchlib import rounds


def round_ms(run) -> Optional[float]:
    """Host wall time per streamed round over the whole window."""
    n = run.values.get("window_rounds", 0)
    return run.window_s / n * 1e3 if n else None


def stream_program_us(run) -> Optional[float]:
    """Device time per execution of the stream round program (the XLA
    module the runner names in ``values["stream_program"]``) in the
    traced window.  The program runs once per streamed round, so the
    trace has to hold as many executions as the window had rounds: no
    more (the window's edges may cut one round on either side), and no
    fewer than nine in ten (where the profiler dropped events)."""
    if run.trace is None:
        return None
    name = run.values["stream_program"]
    hits = [(n, t) for mod, (n, t) in run.trace.modules.items()
            if mod == name or mod.startswith(name + "(")]
    if not hits:
        raise RuntimeError(f"the stream program {name} is not in the "
                           f"trace (modules: {sorted(run.trace.modules)})")
    count = sum(n for n, _ in hits)
    total = sum(t for _, t in hits)
    rounds_ = run.values["window_rounds"]
    if not 0.9 * rounds_ <= count <= rounds_ + 2:
        raise RuntimeError(f"the trace holds {count} executions of {name} "
                           f"against {rounds_} rounds in the window")
    return total / count * 1e6


def idle_percent(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def percentile(run, key: str, q: float) -> Optional[float]:
    values = run.values.get(key)
    if values is None or len(values) == 0:
        return None
    return rounds.pct(values, q)
