"""Quickstart: the paper's factorizations + a tiny LM train loop.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.core import lu as L
from repro.core.lookahead import get_variant
from repro.data.pipeline import SyntheticTask
from repro.train.trainer import Trainer, TrainerConfig


def factorize_demo():
    print("=== DMF with static look-ahead (paper §4) ===")
    rng = np.random.default_rng(0)
    n, b = 512, 128
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))

    for variant in ("mtb", "la"):
        fn = jax.jit(lambda x, v=variant: get_variant("lu", v)(x, b))
        fac, piv = fn(a)
        l, u = L.unpack_lu(fac)
        perm = L.permutation_from_pivots(piv, n)
        err = jnp.linalg.norm(a[perm] - l @ u) / jnp.linalg.norm(a)
        print(f"LU [{variant:3s}]  ‖PA−LU‖/‖A‖ = {float(err):.2e}")

    spd = a @ a.T + n * jnp.eye(n)
    lchol = jax.jit(lambda x: get_variant("cholesky", "la")(x, b))(spd)
    err = jnp.linalg.norm(spd - lchol @ lchol.T) / jnp.linalg.norm(spd)
    print(f"Cholesky [la]  ‖A−LLᵀ‖/‖A‖ = {float(err):.2e}")


def train_demo():
    print("\n=== tiny LM training (gemma-7b smoke config) ===")
    cfg = reduced_config(get_config("gemma-7b"))
    src = SyntheticTask(vocab_size=cfg.vocab_size, seq_len=32, noise=0.05)
    tr = Trainer(cfg, TrainerConfig(steps=30, per_device_batch=8,
                                    peak_lr=2e-3, warmup_steps=5,
                                    log_every=10), src)
    hist = tr.run()
    print(f"loss: {hist[0]:.3f} -> {hist[-1]:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    factorize_demo()
    train_demo()
