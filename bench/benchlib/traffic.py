"""The one load generator: a traffic mix is a data file of parameters.

A mix file (``bench/traffic/<mix>.json``) holds an ``arrivals`` kind and
its parameters.  Kinds:

* ``backlogged`` -- closed at the queue: before every round each offered
  sender's queue is topped up to ``top_up`` messages, so the protocol's
  window, not the offer, limits publishing.
* ``poisson`` -- open loop on the wall clock: ``rate_msgs_per_s``
  messages per second in all, each offered sender a Poisson stream of
  its own (exponential gaps) at an equal share of the rate.
* ``onoff`` -- open loop, bursty: each offered sender alternates
  exponentially long ON and OFF periods (means ``mean_on_s`` and
  ``mean_off_s``), sending as a Poisson stream at ``rate_on`` or
  ``rate_off`` messages per second per sender.  The pattern of the
  program's ``repro.load.arrivals.OnOff``, on the wall clock.

``senders`` names the offered senders: ``"all"`` or a list of ranks.
Arrival times are drawn from the seed before the window opens; a round
takes in every arrival due by the time it starts, so each message keeps
its due time and the harness can time it from then.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(stream)]))


def _offered(spec, n_senders: int) -> np.ndarray:
    if spec in (None, "all"):
        return np.arange(n_senders)
    ranks = np.asarray(sorted(set(int(r) for r in spec)), np.int64)
    if ranks.size == 0 or ranks[0] < 0 or ranks[-1] >= n_senders:
        raise ValueError(f"offered senders {spec} outside 0..{n_senders-1}")
    return ranks


def _poisson_times(rate: float, horizon: float,
                   rng: np.random.Generator) -> np.ndarray:
    """Arrival times of a Poisson stream of ``rate`` per second in
    ``[0, horizon]``."""
    if rate <= 0 or horizon <= 0:
        return np.zeros(0)
    mean = rate * horizon
    n = int(mean + 10 * np.sqrt(mean) + 100)
    times = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while times[-1] < horizon:           # never taken at these margins
        more = times[-1] + np.cumsum(rng.exponential(1.0 / rate, size=n))
        times = np.concatenate([times, more])
    return times[times <= horizon]


def _onoff_times(p: Dict, horizon: float,
                 rng: np.random.Generator) -> np.ndarray:
    mean_on, mean_off = float(p["mean_on_s"]), float(p["mean_off_s"])
    on = rng.random() < mean_on / (mean_on + mean_off)
    t, out = 0.0, []
    while t < horizon:
        length = rng.exponential(mean_on if on else mean_off)
        end = min(t + length, horizon)
        rate = float(p["rate_on"] if on else p["rate_off"])
        out.append(t + _poisson_times(rate, end - t, rng))
        t, on = end, not on
    return np.concatenate(out) if out else np.zeros(0)


class Offer:
    """The offered load of one run.

    ``take(now, backlog)`` gives the (S,) messages that become ready at
    a round starting ``now`` seconds into the window; ``due`` (open-loop
    kinds) holds each sender's arrival times in FIFO order, of which the
    first ``taken[s]`` have been handed out."""

    def __init__(self, mix: Dict, n_senders: int, seed: int,
                 horizon_s: float):
        self.kind = mix["arrivals"]
        self.n_senders = n_senders
        self.offered = _offered(mix.get("senders", "all"), n_senders)
        self.top_up = None
        self.due: Optional[List[np.ndarray]] = None
        if self.kind == "backlogged":
            self.top_up = int(mix["top_up"])
        elif self.kind in ("poisson", "onoff"):
            self.due = [np.zeros(0) for _ in range(n_senders)]
            rate = float(mix.get("rate_msgs_per_s", 0.0))
            for r in self.offered:
                rng = _rng(seed, int(r))
                self.due[r] = (
                    _poisson_times(rate / len(self.offered), horizon_s, rng)
                    if self.kind == "poisson"
                    else _onoff_times(mix, horizon_s, rng))
            stamps = np.concatenate(self.due)
            owners = np.concatenate([np.full(len(d), r)
                                     for r, d in enumerate(self.due)])
            order = np.argsort(stamps, kind="stable")
            self._stamps = stamps[order]
            self._owners = owners[order]
            self._next = 0
            self.taken = np.zeros(n_senders, np.int64)
            self.late_s: List[np.ndarray] = []
        else:
            raise ValueError(f"unknown arrivals kind {self.kind!r}")

    def take(self, now: float, backlog: np.ndarray) -> np.ndarray:
        ready = np.zeros(self.n_senders, np.int64)
        if self.top_up is not None:
            want = self.top_up - np.asarray(backlog, np.int64)
            ready[self.offered] = np.maximum(want[self.offered], 0)
            return ready
        hi = int(np.searchsorted(self._stamps, now, side="right"))
        lo, self._next = self._next, hi
        if hi > lo:
            ready += np.bincount(self._owners[lo:hi],
                                 minlength=self.n_senders)
            self.late_s.append(now - self._stamps[lo:hi])
            self.taken += ready
        return ready

    def lateness(self) -> Optional[np.ndarray]:
        """Seconds by which each handed-out arrival was taken late (the
        generator's lateness), or None for a backlogged offer."""
        if self.due is None:
            return None
        return (np.concatenate(self.late_s) if self.late_s
                else np.zeros(0))
