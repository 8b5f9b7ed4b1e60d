"""Per-message accounting from a stream's round traces.

Copied from the program's ``repro.load.metrics`` (``sender_app_timeline``,
``delivered_watermark``, ``_pct``) so that the benchmark's arithmetic
cannot change with the program.  The protocol does not time messages;
its round traces fix every message's life, because the total order is
round-robin arithmetic:

* sender ``s``'s ``j``-th app message publishes in the round where its
  app count reaches ``j+1``; its index among the sender's publishes
  (apps and nulls, apps first within a round) puts it at total-order
  seq ``index * S + s``;
* it is delivered everywhere in the first round in which every member's
  delivered watermark reaches that seq.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def sender_app_timeline(app_pub_s: np.ndarray, nulls_s: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """One sender's (T,) app and null publish counts -> per app message
    ``(publish_round, publish_index)``."""
    a = np.asarray(app_pub_s, np.int64)
    nl = np.asarray(nulls_s, np.int64)
    app_cum = np.cumsum(a)
    tot_start = np.cumsum(a + nl) - (a + nl)
    app_start = app_cum - a
    rounds = np.repeat(np.arange(a.shape[0]), a)
    j = np.arange(int(app_cum[-1]) if a.size else 0)
    idx = tot_start[rounds] + (j - app_start[rounds])
    return rounds, idx


def delivered_watermark(batches: np.ndarray) -> np.ndarray:
    """(T, N) seqs delivered per round and member -> (T,) highest seq
    delivered at every member by the end of each round."""
    if batches.shape[0] == 0:
        return np.zeros(0, np.int64)
    per_member = np.cumsum(np.asarray(batches, np.int64), axis=0) - 1
    return per_member.min(axis=1)


def delivery_rounds(app_pub: np.ndarray, nulls: np.ndarray,
                    batches: np.ndarray) -> list:
    """Per sender, the round in which each of its app messages (in
    publish order) was delivered at every member; ``T`` (one past the
    last round) for a message not delivered everywhere."""
    n_s = app_pub.shape[1]
    dmin = delivered_watermark(batches)
    out = []
    for s in range(n_s):
        _, idx = sender_app_timeline(app_pub[:, s], nulls[:, s])
        out.append(np.searchsorted(dmin, idx * n_s + s))
    return out


def pct(values: np.ndarray, q: float) -> float:
    values = np.asarray(values)
    return float(np.percentile(values, q)) if values.size else 0.0
