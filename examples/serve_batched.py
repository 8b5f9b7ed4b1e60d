"""Serving example: replicated continuous batching ON the multicast
substrate (DESIGN.md Sec. 6).

Two replica engines decode a staggered request stream while every round's
admissions and emitted tokens are published as DDS messages — one topic
per replica, slot == SMC sender rank — through ONE stacked compiled
program per engine round (`Domain.bind` -> `GroupStream`).  The demo
shows:

  * opportunistic admission (no waiting for a full batch) with slot reuse
    gated on the multicast delivery watermark (a freed KV slot re-admits
    only once its response is delivered at every subscriber);
  * a client backpressure window (replica 0, slot 0 stalls for three
    rounds) covered by null-send rounds — the other slots' tokens keep
    delivering;
  * the merged report: tokens/s next to multicast duration / RDMA writes.

Run:  PYTHONPATH=src python examples/serve_batched.py
"""

import jax
import numpy as np

from repro import use_compilation_cache
from repro.models import layers, registry
from repro.models.runtime import Runtime
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.fanout import ReplicatedEngine


def main():
    use_compilation_cache()
    arch = registry.get("qwen3-1.7b")
    cfg = arch.cfg.reduced()
    params = layers.init_tree(registry.param_specs(cfg), jax.random.key(0))
    engines = [ServeEngine("qwen3-1.7b", params, cfg,
                           EngineConfig(max_batch=3, max_len=96),
                           Runtime())
               for _ in range(2)]

    def stall(replica, rnd):             # client backpressure window
        return (0,) if (replica == 0 and 3 <= rnd < 6) else ()

    rep = ReplicatedEngine(engines, subscribers_per_replica=2, window=4,
                           stall_fn=stall)
    rng = np.random.default_rng(0)
    for g in range(2):
        for i in range(5):               # more requests than slots
            rep.submit(g, Request(
                rid=g * 10 + i,
                prompt=rng.integers(0, cfg.vocab_size, 5, dtype=np.int32),
                max_new_tokens=6 + 2 * (i % 2)))

    report = rep.run()
    serve = report.extras["serve"]
    print(f"served {serve['requests']} requests / {serve['tokens']} tokens"
          f" in {serve['engine_rounds']} engine rounds "
          f"({serve['tokens_per_s']:.1f} tok/s wall)")
    print(f"multicast: {report.delivered_app_msgs} app deliveries, "
          f"{report.nulls_sent} nulls sent (stalled rounds: "
          f"{serve['stall_rounds']}), {report.rdma_writes} RDMA writes, "
          f"{report.duration_us:.0f} us modeled duration")
    for g, streams in sorted(rep.completed().items()):
        for i, toks in enumerate(streams):
            print(f"  replica {g} req {i}: {len(toks)} tokens "
                  f"-> {toks[:5]}...")
    refills = {rid: rnd for rid, rnd in rep.admit_rounds.items() if rnd}
    print(f"watermark-gated refills (rid -> engine round): {refills}")
    assert serve["requests"] == 10 and serve["held_slots"] == 0


if __name__ == "__main__":
    main()
