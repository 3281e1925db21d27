"""End-to-end training driver (deliverable b): train an LM for a few hundred
steps with checkpointing + fault-tolerance wired in.

    # ~20M-param model, a few hundred steps (CPU-feasible):
    PYTHONPATH=src python examples/train_lm.py --preset 20m --steps 300

    # ~100M-param model (the assignment's reference size; give it time on CPU
    # or run on a real accelerator):
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import argparse
import dataclasses

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.data.pipeline import SyntheticTask
from repro.train.trainer import Trainer, TrainerConfig

PRESETS = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)  ≈ params
    "2m": (2, 128, 4, 4, 512, 2048),
    "20m": (8, 384, 6, 6, 1536, 8192),
    "100m": (12, 768, 12, 12, 3072, 16384),
}


def make_cfg(preset: str):
    base = get_config("qwen2-72b")          # llama-style dense backbone
    nl, d, h, kv, f, v = PRESETS[preset]
    cfg = dataclasses.replace(
        base, name=f"train-lm-{preset}", num_layers=nl, d_model=d,
        num_heads=h, num_kv_heads=kv, head_dim=d // h, d_ff=f, vocab_size=v,
        qkv_bias=False, dtype="float32", remat=False,
        attn_chunk_q=256, attn_chunk_k=256)
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="2m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "shampoo"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = make_cfg(args.preset)
    print(f"{cfg.name}: {cfg.param_count():,} params")
    src = SyntheticTask(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                        noise=0.02)
    tc = TrainerConfig(steps=args.steps, per_device_batch=args.batch,
                       optimizer=args.optimizer, peak_lr=args.lr,
                       warmup_steps=max(10, args.steps // 20),
                       ckpt_dir=args.ckpt_dir, ckpt_every=100, log_every=10)
    trainer = Trainer(cfg, tc, src)
    hist = trainer.run()
    print(f"final loss {hist[-1]:.4f} (from {hist[0]:.4f}); "
          f"median step {trainer.watchdog.median*1e3:.0f} ms; "
          f"straggler flags {trainer.watchdog.flags}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
