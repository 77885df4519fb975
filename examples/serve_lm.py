"""Serve a (reduced) assigned architecture with batched greedy decoding:
prefill a prompt batch, then decode tokens against the KV/state cache.

  PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x7b --tokens 16
Every one of the 10 assigned architectures works (--arch <id>).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, list_archs
from repro.models.transformer import model as M
from repro.train import lm_trainer


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    print(f"serving {args.arch} (reduced: {cfg.num_layers}L "
          f"d={cfg.d_model} V={cfg.vocab_size})")
    params = M.init_params(jax.random.key(0), cfg)

    B, T = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, T), 0,
                                          cfg.vocab_size),
             "labels": jnp.zeros((B, T), jnp.int32)}
    if cfg.num_patch_tokens:
        batch["patch_embeds"] = jnp.zeros((B, cfg.num_patch_tokens,
                                           cfg.d_model))
    if cfg.is_encoder_decoder:
        batch["frame_embeds"] = jax.random.normal(
            jax.random.key(2), (B, cfg.num_frame_tokens, cfg.d_model))

    # prefill builds the cache at prompt length + decode budget
    prefill = jax.jit(lm_trainer.make_prefill_step(cfg))
    serve = jax.jit(lm_trainer.make_serve_step(cfg))
    t0 = time.time()
    logits, caches = prefill(params, batch)
    # grow caches: re-init at full length and replay prompt (simple path;
    # uses the jitted serve step so the replay compiles once)
    cache = M.init_cache(cfg, B, T + args.tokens)
    for t in range(T):
        _, _, cache = serve(params, cache, batch["tokens"][:, t:t+1],
                            jnp.int32(t))
    print(f"prefill({T} tokens): {time.time()-t0:.2f}s")

    token = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
    out = [token]
    t0 = time.time()
    for i in range(args.tokens - 1):
        token, logits, cache = serve(params, cache, token,
                                     jnp.int32(T + i))
        out.append(token)
    dt = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"decoded {args.tokens} tokens/seq x {B} seqs in {dt:.2f}s "
          f"({args.tokens*B/max(dt,1e-9):.1f} tok/s on 1 CPU core)")
    print("generated ids:", gen.tolist())


if __name__ == "__main__":
    main()
