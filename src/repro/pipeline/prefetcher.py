"""Background minibatch preparation: deterministic plan + bounded prefetch.

Determinism contract: every minibatch is a pure function of
``(base_seed, epoch, step)`` — each step owns a private
``np.random.Generator`` seeded from that triple, and the per-epoch shuffle
of each rank's training seeds likewise owns a per-``(epoch, rank)`` stream.
Worker threads therefore never share RNG state, so the produced batches are
bit-identical whether sampling runs inline (``num_workers=0``), on one
worker, or on eight — the property ``tests/test_pipeline.py`` pins.

Rank imbalance: an epoch takes ``max_r ceil(train_r / batch)`` steps on
every rank (the trainer's collectives are synchronous).  Ranks that run out
of seeds contribute *empty* seed batches — fully masked minibatches that add
zero examples to the step — instead of silently re-training earlier seeds.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.configs.gnn import GNNConfig
from repro.graph.partition import PartitionSet
from repro.graph.sampling import epoch_minibatches, pad_schedule
from repro.pipeline.vectorized_sampler import (sample_blocks_vectorized,
                                               stack_ranks)

# domain-separation tags so shuffle and sampling streams never collide
_SHUFFLE_TAG = 0x5F
_SAMPLE_TAG = 0xA7


@dataclasses.dataclass
class SamplingPlan:
    """Deterministic schedule of per-rank seed batches + per-step RNG streams."""
    ps: PartitionSet
    cfg: GNNConfig
    base_seed: int = 0
    # resilience fault injector (repro.resilience.FaultInjector): lets a
    # scheduled kill_prefetch fault crash the worker drawing an exact
    # (epoch, step) — exercised by the prefetch retry path below
    injector: Optional[object] = None

    def epoch_schedule(self, epoch: int) -> List[List[np.ndarray]]:
        """``schedule[step][rank]`` -> seed VID_p array (empty when padded)."""
        bs = self.cfg.batch_size
        per_rank = []
        for r, part in enumerate(self.ps.parts):
            rng = np.random.default_rng(
                [self.base_seed, epoch, r, _SHUFFLE_TAG])
            per_rank.append(epoch_minibatches(part, bs, rng))
        return pad_schedule(per_rank)

    def step_rng(self, epoch: int, step: int) -> np.random.Generator:
        return np.random.default_rng(
            [self.base_seed, epoch, step, _SAMPLE_TAG])

    def sample_host(self, epoch: int, step: int,
                    seed_lists: Sequence[np.ndarray]) -> dict:
        """One synchronized [R, ...] host minibatch for ``(epoch, step)``."""
        cfg = self.cfg
        if self.injector is not None:
            # raises PrefetchWorkerKilled exactly once per scheduled
            # fault — the retry of the same (epoch, step) then succeeds
            self.injector.prefetch_crash(epoch, step)
        rng = self.step_rng(epoch, step)
        # the two host phases of minibatch preparation, timed separately:
        # CSR fanout sampling vs the [R, ...] stacking/padding host prep
        # (spans run on whichever prefetch worker executes the step)
        with obs.span("sample", epoch=epoch, step=step):
            mbs = [sample_blocks_vectorized(part, seed_lists[r], cfg.fanouts,
                                            rng, cfg.batch_size)
                   for r, part in enumerate(self.ps.parts)]
        with obs.span("host_prep", epoch=epoch, step=step):
            return stack_ranks(mbs)


def prefetch(make_fn: Callable[[int], dict], num_steps: int,
             num_workers: int, depth: int) -> Iterator[dict]:
    """Yield ``make_fn(0..num_steps-1)`` in order, up to ``depth`` in flight.

    ``num_workers <= 0`` degrades to fully synchronous inline calls (the
    pipeline's reference path).  Work is submitted to a thread pool and
    results are consumed strictly in step order; because each step owns its
    RNG stream (see ``SamplingPlan``), the output sequence is identical for
    any worker count.

    Worker-crash containment: a worker exception only surfaces here, when
    its future is consumed mid-epoch.  The step's draw is retried ONCE,
    inline — deterministic per-step RNG makes the retry produce the exact
    batch the dead worker would have — counted as ``prefetch_retries`` in
    the registry; a second failure propagates (a real bug, not a flake).
    """
    if num_workers <= 0:
        for step in range(num_steps):
            yield make_fn(step)
        return
    depth = max(depth, 1)
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=num_workers, thread_name_prefix="minibatch-prefetch")
    try:
        inflight = collections.deque()
        nxt = 0
        while nxt < num_steps and len(inflight) < depth:
            inflight.append((nxt, pool.submit(make_fn, nxt)))
            nxt += 1
        while inflight:
            step, fut = inflight.popleft()
            try:
                batch = fut.result()
            except Exception:
                obs.count("prefetch_retries")
                batch = make_fn(step)
            if nxt < num_steps:
                inflight.append((nxt, pool.submit(make_fn, nxt)))
                nxt += 1
            yield batch
    finally:
        # consumer may abandon the generator mid-epoch (error in the train
        # step): drop queued work instead of sampling batches nobody wants
        pool.shutdown(wait=True, cancel_futures=True)
