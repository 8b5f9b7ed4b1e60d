#!/usr/bin/env python3
"""Show the fault that keeps the serve plane out of the benchmark.

The fused serve path (``ReplicatedEngine.run(fused=True)``) prefills a
request's prompt ``p[0..n-1]`` at positions ``0..n-1`` and then starts
decoding by feeding the last prompt token ``p[n-1]`` once more, at
position ``n``.  The model therefore continues ``p + [p[n-1]]``, not
``p``, and every served token is the continuation of a sequence the
client never sent.

For each request this prints three readings of the plain float32
reference (:mod:`benchlib.qwen_ref`), on weights drawn from the seed:

* ``gap_prompt`` -- the widest gap by which a served token's logit lies
  below the reference's best, reading the reference over the prompt and
  the served tokens (what a client asked for);
* ``gap_repeated`` -- the same over ``p + [p[n-1]] + served[:-1]`` (the
  sequence the program fed itself): small, so the duplicated token, not
  the arithmetic, is the cause;
* ``first_token`` -- the first served token beside the reference's
  argmax after the prompt and beside the argmax of the program's own
  full-sequence prefill (``transformer.prefill``), a second witness
  that sides with the reference.

    python3 bench/tools/serve_fault.py --sizes bench/tools/qwen1.5-0.5b.json \
        --seeds 1 2 3 [--tiny]

``--tiny`` runs the program's reduced qwen1.5-0.5b on the CPU.
"""

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import harness, qwen_ref  # noqa: E402


def serve(cfg, params, prompts, new_tokens, *, replicas, slots, max_len,
          window, subscribers):
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    from repro.serve.fanout import ReplicatedEngine

    engines = [ServeEngine("qwen1.5-0.5b", params, cfg,
                           EngineConfig(max_batch=slots, max_len=max_len))
               for _ in range(replicas)]
    rep = ReplicatedEngine(engines, subscribers_per_replica=subscribers,
                           window=window)
    for i, p in enumerate(prompts):
        rep.submit(i % replicas, Request(rid=i, prompt=p.copy(),
                                         max_new_tokens=new_tokens))
    report = rep.run(fused=True)
    if not report.extras["serve"]["fused"]:
        raise RuntimeError("the serve run left the fused path")
    out = {}
    for g, toks in rep.completed().items():
        for j, t in enumerate(toks):
            out[j * replicas + g] = np.asarray(t, np.int64)
    return out


def witness(sz, cfg, seed, n_requests, prompt_len, new_tokens, **shape):
    import jax
    from repro.models import registry, transformer
    from repro.models.runtime import Runtime

    params = qwen_ref.draw_weights(sz, seed)
    want = jax.tree.map(lambda s: tuple(s.shape), registry.param_specs(cfg),
                        is_leaf=lambda x: hasattr(x, "axes"))
    got = jax.tree.map(lambda x: tuple(x.shape), params)
    if want != got:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{want} vs {got}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, sz["vocab_size"], size=int(
        rng.integers(prompt_len[0], prompt_len[1] + 1))).astype(np.int32)
        for _ in range(n_requests)]
    served = serve(cfg, params, prompts, new_tokens, **shape)
    prefill_first = {i: int(np.argmax(np.asarray(transformer.prefill(
        params, cfg, p[None, :], Runtime())[0][0], np.float32)))
        for i, p in enumerate(prompts)}
    del params
    ref = qwen_ref.Reference(sz, qwen_ref.draw_weights(sz, seed))
    rows = []
    for i, p in enumerate(prompts):
        s, n = served[i], len(p)
        asked = ref.logits(np.concatenate([p, s]))[n - 1: n - 1 + len(s)]
        fed = ref.logits(np.concatenate([p, p[-1:], s[:-1]]))[n: n + len(s)]
        rows.append({
            "seed": seed, "request": i, "prompt_len": n,
            "served": len(s),
            "gap_prompt": float(qwen_ref.served_gap(asked, s).max()),
            "gap_repeated": float(qwen_ref.served_gap(fed, s).max()),
            "first_token": {"served": int(s[0]),
                            "reference": int(np.argmax(asked[0])),
                            "program_prefill": prefill_first[i]}})
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default=os.path.join(
        BENCH, "tools", "qwen1.5-0.5b.json"))
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    harness.import_program()
    from repro.models import registry

    sizes = harness.load_json(args.sizes)
    cfg = registry.get("qwen1.5-0.5b").cfg
    shape = dict(replicas=2, slots=8, max_len=640, window=4,
                 subscribers=2)
    prompt_len, new_tokens = (32, 256), 32
    if args.tiny:
        cfg = cfg.reduced()
        sizes = dict(sizes, **sizes["tiny"])
        shape.update(slots=2, max_len=64)
        prompt_len, new_tokens = (4, 24), 8
    sizes["rope_theta"] = cfg.rope_theta
    for seed in args.seeds:
        for row in witness(sizes, cfg, seed, args.requests, prompt_len,
                           new_tokens, **shape):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
