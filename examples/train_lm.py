"""End-to-end training driver: train a ~100M-parameter dense LM for a few
hundred steps on the deterministic synthetic stream, with checkpointing
and a mid-run restart to prove restore-from-watermark.

Run:  PYTHONPATH=src python examples/train_lm.py            # ~100M, 300 steps
      PYTHONPATH=src python examples/train_lm.py --tiny     # CI-sized
"""

import argparse
import dataclasses
import tempfile

from repro import use_compilation_cache
from repro.models import registry
from repro.models.config import ModelConfig
from repro.models.runtime import Runtime
from repro.optim.adamw import OptConfig
from repro.train.trainer import TrainConfig, Trainer


def model_100m() -> ModelConfig:
    """~109M params, qwen-style dense decoder."""
    return ModelConfig(
        name="dense-100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=4, d_ff=2048, vocab_size=32000,
        head_dim=64, tie_embeddings=True)


def model_tiny() -> ModelConfig:
    return ModelConfig(
        name="dense-tiny", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512, head_dim=32,
        tie_embeddings=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    use_compilation_cache()

    cfg = model_tiny() if args.tiny else model_100m()
    registry.register(cfg.name, lambda c=cfg: c)
    steps = args.steps or (60 if args.tiny else 300)
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="spindle_ckpt_")

    tcfg = TrainConfig(
        steps=steps,
        seq_len=64 if args.tiny else 256,
        global_batch=4 if args.tiny else 8,
        checkpoint_dir=ckpt,
        checkpoint_every=max(steps // 4, 10),
        log_every=max(steps // 20, 5),
        data_patterns=8 if args.tiny else 64,
        opt=OptConfig(peak_lr=3e-3 if args.tiny else 1e-3,
                      warmup_steps=20, decay_steps=steps),
    )
    print(f"training {cfg.name} for {steps} steps "
          f"(checkpoints -> {ckpt})")
    trainer = Trainer(cfg.name, cfg, tcfg, Runtime())
    trainer.run()
    first = trainer.history[0]["loss"]
    last = trainer.history[-1]["loss"]
    print(f"loss: {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")

    # restart-from-watermark proof: a fresh trainer resumes, not restarts
    trainer2 = Trainer(cfg.name, cfg, dataclasses.replace(
        tcfg, steps=steps + max(steps // 10, 5)), Runtime())
    print("restarting from the checkpoint watermark ...")
    trainer2.run()
    print(f"resumed at step {steps} and reached "
          f"{trainer2.history[-1]['step']} "
          f"(loss {trainer2.history[-1]['loss']:.3f})")


if __name__ == "__main__":
    main()
