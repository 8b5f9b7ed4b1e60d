"""Batched serving engine with continuous batching and round-robin
delivery (the paper's protocol shape, applied to inference).

Mapping (DESIGN.md Sec. 6): requests are messages; the decode loop is the
predicate sweep — every iteration it *opportunistically batches* whatever
is ready (admits new requests into free KV-cache slots = SMC ring slots,
decodes every active slot in one fused step); a slot is freed only after
its response is delivered (slot-reuse rule).  A request that stalls
(client backpressure) occupies its slot but decodes a null step — the
batch round never waits (null-round analogue).  The multicast side of the
mapping — each round's admissions and emitted tokens published on a DDS
topic per replica, swept by ONE stacked program — lives in
:mod:`repro.serve.fanout`.

Single-host reference implementation; the decode step itself is the same
``make_serve_step`` the multi-pod dry-run lowers, so the engine scales to
the production mesh by construction.

Every decode step is validity-masked (:mod:`repro.models.masking`): a
slot that is idle, stalled, or merely a bystander to another slot's
prefill carries its decode state through bit-unchanged instead of taking
a garbage write.  For position-addressed state (KV caches) that is
output-equivalent to the old write-then-overwrite dance; for recurrent
families (ssm/hybrid), whose state mutates cumulatively every step, it
is the unlock — every registry family now serves through the same slot
ring (DESIGN.md Sec. 6).  The masked step is also exactly the round
body the fused device-resident serve plane scans
(:mod:`repro.serve.fused`).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers, masking, registry
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.runtime import Runtime

# Compile options of every program that runs the decode body.  XLA on a
# TPU otherwise keeps bf16 intermediates in f32 inside a fusion and
# rounds only at its edge, so the same body compiled into two programs
# (the per-round step here, the fused serve loop) rounds at different
# points and near-tied logits argmax differently.  Rounding every op as
# written makes the result independent of fusion: the two paths agree
# bit for bit on the chip as on the CPU.
DECODE_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S_prompt,) int32
    max_new_tokens: int = 16
    submitted_at: float = 0.0
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    finished_at: Optional[float] = None


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8                  # KV slots (the ring window w)
    max_len: int = 256
    eos_id: Optional[int] = None
    greedy: bool = True


@dataclasses.dataclass
class EngineRound:
    """What one :meth:`ServeEngine.step` did — the per-round event record
    the serve fan-out publishes as multicast messages (one message per
    admission, one per emitted token; see :mod:`repro.serve.fanout`)."""

    admitted: List[int] = dataclasses.field(default_factory=list)  # slots
    admitted_rids: List[int] = dataclasses.field(default_factory=list)
    emitted: List[int] = dataclasses.field(default_factory=list)   # slots
    finished: List[int] = dataclasses.field(default_factory=list)  # slots
    finished_rids: List[int] = dataclasses.field(default_factory=list)
    stalled: List[int] = dataclasses.field(default_factory=list)   # slots

    def __bool__(self) -> bool:          # truthy = the round made progress
        return bool(self.admitted or self.emitted)


class ServeEngine:
    """Continuous-batching decode engine over a fixed slot ring."""

    def __init__(self, arch_name: str, params, cfg: ModelConfig,
                 ecfg: EngineConfig, rt: Runtime = Runtime()):
        self.arch = registry.get(arch_name)
        self.cfg = cfg
        self.ecfg = ecfg
        self.rt = rt
        self.params = params
        b, s = ecfg.max_batch, ecfg.max_len
        shape = ShapeConfig("engine", s, b, "decode")
        self.cache_specs = registry.cache_specs(cfg, shape,
                                                batch_override=b)
        self.cache = jax.tree.map(
            lambda sp: jnp.zeros(sp.shape, sp.dtype), self.cache_specs,
            is_leaf=lambda x: isinstance(x, layers.ParamSpec))
        decode_fn, specs = self.arch.decode_fn(), self.cache_specs

        def _decode_body(p, c, t, pos, valid):
            """One masked decode step: slots where ``valid`` advance
            their state; the rest carry it through bit-unchanged (the
            null-round no-op — what lets recurrent families serve).
            This pure body is shared verbatim with the fused serve
            program (:mod:`repro.serve.fused`), so the fused scan and
            this per-round loop run the same arithmetic."""
            logits, new_c = decode_fn(p, cfg, c, t, pos, rt)
            return logits, masking.masked_update(specs, c, new_c, valid)

        def _reset_body(c, valid):
            """Admission reset: zero the admitted slots' cache rows (a
            no-op for the rest).  Shared with the fused program, like
            ``_decode_body`` — see :func:`repro.models.masking.reset_rows`
            for why recurrent families require it."""
            return masking.reset_rows(specs, c, valid)

        self._decode_body = _decode_body
        self._reset_body = _reset_body
        self.decode = jax.jit(_decode_body, donate_argnums=(1,),
                              compiler_options=DECODE_COMPILER_OPTIONS)
        self._reset_slots = jax.jit(_reset_body, donate_argnums=(0,))
        # slot state (the SMC ring of the serving plane)
        self.slot_req: List[Optional[Request]] = [None] * b
        self.slot_len = np.zeros(b, dtype=np.int64)
        self.queue: deque = deque()
        self.completed: List[Request] = []
        self.rounds = 0
        self.decode_steps = 0
        # device->host syncs taken inside decode rounds (the logits
        # readback) — the per-round hop the fused serve plane removes
        self.host_syncs = 0

    # -- request plane -------------------------------------------------------

    def submit(self, req: Request):
        req.submitted_at = req.submitted_at or time.time()
        self.queue.append(req)

    def _admit(self, admit_mask: Optional[Sequence[bool]] = None
               ) -> List[int]:
        """Opportunistic admission: fill every free slot that has a ready
        request (never waits to accumulate a batch).  ``admit_mask``
        restricts which slots may admit this round — the serve fan-out
        gates it on the multicast delivery watermark (slot free = last
        response delivered, the SMC slot-reuse rule).  Returns the slots
        admitted into."""
        admitted = []
        for slot in range(self.ecfg.max_batch):
            if (self.slot_req[slot] is None and self.queue
                    and (admit_mask is None or admit_mask[slot])):
                req = self.queue.popleft()
                self._prefill_slot(slot, req)
                admitted.append(slot)
        return admitted

    def _prefill_slot(self, slot: int, req: Request):
        """Sequential prefill through the decode path (single-host
        reference: correctness over speed; the dry-run's prefill step is
        the production path)."""
        self.slot_req[slot] = req
        self.slot_len[slot] = 0
        b = self.ecfg.max_batch
        valid = np.zeros(b, bool)
        valid[slot] = True                # bystander slots: masked no-op
        self.cache = self._reset_slots(self.cache, jnp.asarray(valid))
        for tok in req.prompt:
            tokens = np.zeros((b, 1), dtype=np.int32)
            tokens[slot, 0] = int(tok)
            pos = jnp.asarray(self.slot_len, jnp.int32)
            logits, self.cache = self.decode(self.params, self.cache,
                                             jnp.asarray(tokens), pos,
                                             jnp.asarray(valid))
            self.slot_len[slot] += 1
            self.decode_steps += 1

    # -- the decode sweep ------------------------------------------------------

    def step(self, *, stalled: Optional[Sequence[int]] = None,
             admit_mask: Optional[Sequence[bool]] = None) -> EngineRound:
        """One engine round: admit ready work, decode every active slot.

        ``stalled`` names slots whose client cannot accept output this
        round (backpressure): they keep their slot but make no progress —
        the null-step analogue; the fused decode never waits for them.
        ``admit_mask`` restricts admission (see :meth:`_admit`).  Returns
        the round's :class:`EngineRound` event record (truthy when any
        slot admitted or decoded — the old boolean contract)."""
        self.rounds += 1
        stalled_set = set(stalled or ())
        info = EngineRound(admitted=self._admit(admit_mask))
        info.admitted_rids = [self.slot_req[s].rid for s in info.admitted]
        info.stalled = sorted(stalled_set & {
            i for i, r in enumerate(self.slot_req) if r is not None})
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and i not in stalled_set]
        if not active:
            return info
        b = self.ecfg.max_batch
        tokens = np.zeros((b, 1), dtype=np.int32)
        for i in active:
            req = self.slot_req[i]
            last = req.tokens_out[-1] if req.tokens_out else \
                int(req.prompt[-1])
            tokens[i, 0] = last
        # one fused decode for the whole ring with per-slot positions;
        # idle/stalled slots are masked no-ops (state carried through
        # bit-unchanged — safe for KV and recurrent families alike)
        valid = np.zeros(b, bool)
        valid[active] = True
        pos = jnp.asarray(self.slot_len, jnp.int32)
        logits, self.cache = self.decode(self.params, self.cache,
                                         jnp.asarray(tokens), pos,
                                         jnp.asarray(valid))
        self.decode_steps += 1
        self.host_syncs += 1             # logits cross device->host below
        logits = np.asarray(logits.astype(jnp.float32))
        for i in active:
            req = self.slot_req[i]
            nxt = int(np.argmax(logits[i]))
            req.tokens_out.append(nxt)
            info.emitted.append(i)
            self.slot_len[i] += 1
            done = (len(req.tokens_out) >= req.max_new_tokens
                    or (self.ecfg.eos_id is not None
                        and nxt == self.ecfg.eos_id)
                    or self.slot_len[i] >= self.ecfg.max_len - 1)
            if done:
                req.finished_at = time.time()
                self.completed.append(req)
                self.slot_req[i] = None    # slot delivered -> reusable
                self.slot_len[i] = 0
                info.finished.append(i)
                info.finished_rids.append(req.rid)
        return info

    def evict(self, slot: int) -> Optional[Request]:
        """Forcibly clear a slot and void its in-flight decode.

        The serve plane calls this when the slot's NODE dies mid-run
        (DESIGN.md Sec. 7): the request's decoded tokens are discarded —
        its unstable published tail died with the slot, and re-admission
        restarts the decode from the prompt on a surviving slot — and
        the request object is returned to the caller for re-admission or
        shed (the policy lives in the fan-out, DESIGN.md Sec. 9).  Stale
        KV entries are position-overwritten on the next prefill, exactly
        as after :meth:`reset`.  Returns ``None`` if the slot was idle.
        """
        req = self.slot_req[slot]
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        if req is not None:
            req.tokens_out = []
        return req

    def drained(self) -> bool:
        return not self.queue and all(r is None for r in self.slot_req)

    def run_until_drained(self, max_rounds: int = 10_000):
        while not self.drained() and self.rounds < max_rounds:
            self.step()
        return self.completed

    def reset(self) -> None:
        """Clear all request/slot state, keeping params and the compiled
        decode program (re-running a scenario skips the jit cost; stale
        KV entries are position-overwritten before any read)."""
        self.slot_req = [None] * self.ecfg.max_batch
        self.slot_len[:] = 0
        self.queue.clear()
        self.completed = []
        self.rounds = 0
        self.decode_steps = 0
        self.host_syncs = 0
