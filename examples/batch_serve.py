"""Batched serving with the continuous-batching scheduler: more requests
than device slots; slots are reused as requests finish.

  PYTHONPATH=src python examples/batch_serve.py --arch qwen2-vl-7b
"""
import argparse
import time

import jax

from repro.configs import get_arch, list_archs
from repro.models.transformer import model as M
from repro.serve.scheduler import Request, serve_requests


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=6)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    params = M.init_params(jax.random.key(0), cfg)
    reqs = [Request(rid=i, prompt=[1 + i, 2 + i, 3 + i],
                    max_tokens=args.tokens) for i in range(args.requests)]
    t0 = time.time()
    reqs, steps = serve_requests(cfg, params, reqs, num_slots=args.slots,
                                 cache_len=64)
    dt = time.time() - t0
    for r in reqs:
        print(f"req {r.rid}: {r.generated}")
    total = sum(len(r.generated) for r in reqs)
    print(f"{args.requests} requests through {args.slots} slots: "
          f"{steps} batched decode steps, {total} tokens in {dt:.1f}s")


if __name__ == "__main__":
    main()
