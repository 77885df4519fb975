"""Data pipeline substrate.

LM side: a deterministic, shardable synthetic token stream (Markov bigram
mixture — learnable, used by examples/train_lm.py and the smoke tests) plus
a host-side prefetching iterator that yields device-ready global batches
sharded over ("pod","data").  GNN minibatches come from
``repro.pipeline.MinibatchPipeline``.
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


class TokenStream:
    """Deterministic synthetic LM data: order-1 Markov chain with noise.

    Every batch is reproducible from (seed, step) — no state to checkpoint
    beyond the step counter, which is how production pipelines behave under
    preemption.
    """

    def __init__(self, vocab_size: int, batch: int, seq: int, seed: int = 0,
                 signal: float = 0.8):
        self.vocab_size = vocab_size
        self.batch = batch
        self.seq = seq
        self.signal = signal
        rng = np.random.default_rng(seed)
        self.table = rng.integers(0, vocab_size, vocab_size).astype(np.int32)
        self.seed = seed

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        B, T, V = self.batch, self.seq, self.vocab_size
        toks = np.empty((B, T), np.int32)
        toks[:, 0] = rng.integers(0, V, B)
        noise = rng.integers(0, V, (B, T))
        coin = rng.random((B, T)) < self.signal
        for t in range(1, T):
            nxt = self.table[toks[:, t - 1]]
            toks[:, t] = np.where(coin[:, t], nxt, noise[:, t])
        labels = np.roll(toks, -1, axis=1)
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}

    def __iter__(self) -> Iterator[dict]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class Prefetcher:
    """Host-side background prefetch of a batch iterator (depth-bounded)."""

    def __init__(self, it: Iterator, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._it = it
        self._done = object()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        try:
            for item in self._it:
                self._q.put(item)
        finally:
            self._q.put(self._done)

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._done:
            raise StopIteration
        return item


def shard_batch(batch: dict, mesh, batch_axes: Optional[dict] = None):
    """Place a host batch on the mesh, batch dim over ("pod","data")."""
    from repro.models.transformer.sharding import axes_to_pspec
    from jax.sharding import NamedSharding

    def place(name, x):
        axes = (batch_axes or {}).get(name, ("batch",) + (None,) * (x.ndim - 1))
        return jax.device_put(
            x, NamedSharding(mesh, axes_to_pspec(axes, x.shape, mesh)))

    return {k: place(k, v) for k, v in batch.items()}
