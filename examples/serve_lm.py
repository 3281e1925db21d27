"""Batched serving demo: prefill + decode with KV cache across arch families.

    PYTHONPATH=src python examples/serve_lm.py [--arch rwkv6-7b]
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.models import api
from repro.serve.engine import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch))   # smoke config: CPU-runnable
    params, _ = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, ServeConfig(
        batch_size=args.batch, max_len=args.prompt_len + args.new_tokens,
        temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    enc = (rng.standard_normal((args.batch, args.prompt_len, cfg.d_model))
           .astype(np.float32) if cfg.is_enc_dec else None)
    tokens, stats = engine.generate(prompts, args.new_tokens, enc_embed=enc)
    print(f"{cfg.name}: {tokens.shape[0]} sequences × {tokens.shape[1]} new "
          f"tokens")
    print(f"prefill {stats['prefill_s']*1e3:.1f} ms | "
          f"decode {stats['decode_tok_per_s']:.1f} tok/s")
    # shared serve-layer schema — same line shape as examples/solve_server.py
    print(f"wall {stats['wall']:.2f} s | {stats['items_per_s']:.1f} tok/s | "
          f"p50 {stats['p50_ms']:.1f} ms | p99 {stats['p99_ms']:.1f} ms")
    print("sample:", tokens[0, :12].tolist())


if __name__ == "__main__":
    enable_compile_cache()
    main()
