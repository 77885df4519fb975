"""End-to-end LM training driver: trains a ~100M-param dense model for a
few hundred steps on synthetic data and shows the loss dropping toward the
unigram floor.

  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ATTN
from repro.train import lm_trainer
from repro.train.optimizer import AdamConfig, adam_init


def make_100m() -> ArchConfig:
    return ArchConfig(
        name="dense-100m", arch_type="dense", source="examples/train_lm.py",
        num_layers=8, d_model=512, num_heads=8, num_kv_heads=4,
        d_ff=2048, vocab_size=8192, pattern=(ATTN,), dtype="float32",
        remat=False, q_chunk=256)


def synthetic_stream(key, batch, seq, vocab):
    """Markov-ish synthetic tokens (learnable bigram structure)."""
    k1, k2 = jax.random.split(key)
    table = jax.random.randint(k1, (vocab,), 0, vocab)
    x0 = jax.random.randint(k2, (batch, 1), 0, vocab)
    toks = [x0]
    for _ in range(seq - 1):
        nxt = table[toks[-1][:, -1:]]
        noise = jax.random.randint(jax.random.fold_in(k2, len(toks)),
                                   (batch, 1), 0, vocab)
        coin = jax.random.bernoulli(jax.random.fold_in(k1, len(toks)),
                                    0.8, (batch, 1))
        toks.append(jnp.where(coin, nxt, noise))
    return jnp.concatenate(toks, axis=1)


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    cfg = make_100m()
    from repro.models.transformer import model as M
    from repro.utils.tree import tree_count_params
    params = M.init_params(jax.random.key(0), cfg)
    print(f"params: {tree_count_params(params)/1e6:.1f}M")
    opt = adam_init(params)
    step = jax.jit(lm_trainer.make_train_step(cfg, AdamConfig(lr=3e-4,
                                                              grad_clip=1.0)))
    key = jax.random.key(1)
    t0 = time.time()
    losses = []
    for i in range(args.steps):
        key, k = jax.random.split(key)
        tokens = synthetic_stream(k, args.batch, args.seq, cfg.vocab_size)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
        if i % 20 == 0 or i == args.steps - 1:
            tok_s = args.batch * args.seq * (i + 1) / (time.time() - t0)
            print(f"step {i:4d}: loss={losses[-1]:.4f} ({tok_s:.0f} tok/s)")
    assert losses[-1] < losses[0], "loss must decrease"
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps")


if __name__ == "__main__":
    main()
