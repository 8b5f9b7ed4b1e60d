"""Plain reference of the atomic multicast round protocol.

A straightforward numpy implementation of one subgroup of Derecho's
shared-state-table (SST) protocol with Spindle's changes (sender
batching, the SMC ring window, null-send), written from the protocol's
description and independent of the program under test: it imports
nothing of it.  One call of :meth:`RoundMirror.step` is one protocol
round for every member at once, with the wire modelled as
one-round-delayed visibility of the other members' SST rows.

The round, for N members of which the first S are the senders (sender
rank i is member i):

1. receive: each member takes in every message it can see published and
   advances its ``received_num`` to the end of the longest complete
   prefix of the round-robin total order (seq ``k*S + s`` is sender
   ``s``'s ``k``-th publish);
2. null-send: a sender with nothing queued publishes just enough nulls
   that its next message would not precede the newest message it has
   received from any other sender;
3. send: a sender publishes as many queued messages as its ring window
   allows (``window`` beyond what every member it can see has
   delivered of its own messages); its own publishes count as received
   at once;
4. deliver: each member delivers up to the smallest ``received_num`` it
   can see (its own current one, the others' from the previous round);
5. the wire: every member's view of the others' rows catches up to this
   round's values.

Messages the window holds back stay queued for later rounds.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def rr_prefix(counts: np.ndarray) -> np.ndarray:
    """Length of the longest complete prefix of the round-robin order,
    per row of ``counts`` (..., S): every sender has ``m`` messages in
    it, and the leading ranks that have one more add one each."""
    s = counts.shape[-1]
    m = counts.min(axis=-1)
    more = counts >= (m[..., None] + 1)
    lead = np.logical_and.accumulate(more, axis=-1).sum(axis=-1)
    return m * s + lead


def own_share(prefix_len: np.ndarray, n_senders: int) -> np.ndarray:
    """How many of sender ``i``'s messages lie in the first
    ``prefix_len[i]`` seqs of the round-robin order."""
    ranks = np.arange(n_senders)
    return prefix_len // n_senders + (ranks < prefix_len % n_senders)


@dataclasses.dataclass
class RoundMirror:
    """State of one subgroup; :meth:`step` runs one round."""

    n_members: int
    n_senders: int
    window: int
    null_send: bool = True

    def __post_init__(self):
        n, s = self.n_members, self.n_senders
        if not 0 < s <= n:
            raise ValueError("need 1 <= senders <= members")
        self.published = np.zeros(s, np.int64)
        self.pub_seen = np.zeros((n, s), np.int64)
        self.received = np.zeros((n, s), np.int64)
        self.received_num = np.full(n, -1, np.int64)
        self.recv_seen = np.full((n, n), -1, np.int64)
        self.delivered_num = np.full(n, -1, np.int64)
        self.deliv_seen = np.full((n, n), -1, np.int64)
        self.backlog = np.zeros(s, np.int64)
        self.apps = np.zeros(s, np.int64)
        self.nulls = np.zeros(s, np.int64)

    def step(self, ready: np.ndarray):
        """One round with ``ready[s]`` new app messages at sender ``s``.
        Returns ``(delivered (N,), app_published (S,), nulls (S,))``:
        how many seqs each member delivered this round, and what each
        sender published."""
        n, s = self.n_members, self.n_senders
        ranks = np.arange(s)
        members = np.arange(n)
        queued = self.backlog + np.asarray(ready, np.int64)

        # 1. receive what is visible
        received = np.maximum(self.received, self.pub_seen)
        received_num = np.maximum(self.received_num,
                                  rr_prefix(received) - 1)

        # 2. null-send: a sender with an empty queue catches up to the
        # newest message it holds from any other sender
        nulls = np.zeros(s, np.int64)
        if self.null_send:
            rows = received[:s]                       # (S, S) senders
            newest = rows - 1                         # index of newest
            # my next index must reach newest[j] (+1 when I precede j)
            need = newest + (ranks[:, None] < ranks[None, :])
            need = np.where(rows > 0, need, 0)
            need[ranks, ranks] = 0
            target = need.max(axis=1)
            nulls = np.maximum(target - (self.published + queued), 0)
            nulls[queued > 0] = 0

        # 3. send within the ring window
        deliv_now = self.deliv_seen.copy()
        deliv_now[members, members] = self.delivered_num
        low = deliv_now[:s].min(axis=1)               # (S,)
        mine_done = own_share(low + 1, s)
        room = np.maximum(mine_done + self.window - self.published, 0)
        apps = np.minimum(queued, room)
        published = self.published + apps + nulls
        received[ranks, ranks] = np.maximum(received[ranks, ranks],
                                            published)
        received_num = np.maximum(received_num, rr_prefix(received) - 1)

        # 4. deliver up to the smallest visible received_num
        recv_now = self.recv_seen.copy()
        recv_now[members, members] = received_num
        delivered_num = np.maximum(self.delivered_num,
                                   recv_now.min(axis=1))
        batch = delivered_num - self.delivered_num

        # 5. the wire: views catch up to this round's rows
        self.pub_seen = np.maximum(self.pub_seen, published[None, :])
        self.recv_seen = np.maximum(recv_now, received_num[None, :])
        self.deliv_seen = np.maximum(self.deliv_seen,
                                     delivered_num[None, :])
        self.published = published
        self.received = received
        self.received_num = received_num
        self.delivered_num = delivered_num
        self.backlog = queued - apps
        self.apps = self.apps + apps
        self.nulls = self.nulls + nulls
        return batch, apps, nulls


@dataclasses.dataclass
class MirrorRun:
    """A reference run's per-round answers and its final delivery log."""

    batches: np.ndarray          # (T, N) seqs delivered per round
    app_pub: np.ndarray          # (T, S)
    nulls: np.ndarray            # (T, S)
    delivered_num: np.ndarray    # (N,) highest delivered seq per member
    is_app: List[np.ndarray]     # per sender: app (True) / null per index
    backlog: np.ndarray          # (S,) still queued at the end


def run_rounds(ready: np.ndarray, *, n_members: int, window: int,
               null_send: bool = True) -> MirrorRun:
    """Drive the mirror over ``ready`` (T, S) and return every round's
    answers and the delivery log: sender ``s``'s publishes in order,
    each round's apps ahead of its nulls."""
    ready = np.asarray(ready, np.int64)
    t_n, s = ready.shape
    mirror = RoundMirror(n_members, s, window, null_send)
    batches = np.zeros((t_n, n_members), np.int64)
    app_pub = np.zeros((t_n, s), np.int64)
    nulls = np.zeros((t_n, s), np.int64)
    for t in range(t_n):
        batches[t], app_pub[t], nulls[t] = mirror.step(ready[t])
    is_app = []
    for r in range(s):
        per_round = np.stack([app_pub[:, r], nulls[:, r]], axis=1)
        flags = np.repeat(np.tile([True, False], t_n), per_round.ravel())
        is_app.append(flags)
    return MirrorRun(batches=batches, app_pub=app_pub, nulls=nulls,
                     delivered_num=mirror.delivered_num.copy(),
                     is_app=is_app, backlog=mirror.backlog.copy())
