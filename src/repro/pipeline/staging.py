"""Double-buffered host->device staging + the `MinibatchPipeline` iterator.

The one minibatch source for training and evaluation (paper §3.3 sampler
+ §3.4 overlap, DistDGL/MassiveGNN-style prefetching):

    vectorized CSR sampler (host draw) --> prefetch thread pool
    (deterministic per-step RNG streams, bounded depth) -->
    double-buffered ``jax.device_put`` --> compiled shard_map train step

Double buffering exploits jax's asynchronous dispatch: while the device
executes step ``k``, the host has already issued the transfer for step
``k+1``, so sampling *and* H2D copies hide behind compute.  With
``num_workers=0`` sampling runs inline on the loop thread and produces
bit-identical batches.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence

import jax
import numpy as np

from repro import obs
from repro.configs.gnn import GNNConfig
from repro.graph.partition import PartitionSet
from repro.pipeline.prefetcher import SamplingPlan, prefetch

_EVAL_EPOCH_TAG = 1 << 20   # eval streams live far away from training epochs


def device_stage(host_batches: Iterator[dict],
                 sharding=None) -> Iterator[dict]:
    """Map host minibatches to device, keeping one transfer in flight.

    ``jax.device_put`` is dispatched asynchronously, so issuing the put for
    batch ``k+1`` before yielding batch ``k`` overlaps the H2D copy with the
    consumer's device step.  ``sharding`` (e.g. ``NamedSharding(mesh,
    P("data"))``) lands the [R, ...] batch directly in its per-rank layout,
    so the shard_map'd step doesn't reshard on the critical path.
    """
    raw_put = (lambda h: jax.device_put(h, sharding)) \
        if sharding is not None else jax.device_put

    def put(host):
        # device_put dispatches asynchronously: the span measures the
        # host-side staging cost (layout + transfer issue), which is the
        # part that can sit on the step loop's critical path
        with obs.span("stage"):
            return raw_put(host)

    staged = None
    for host in host_batches:
        nxt = put(host)
        if staged is not None:
            yield staged
        staged = nxt
    if staged is not None:
        yield staged


class MinibatchPipeline:
    """Asynchronous minibatch source for ``DistTrainer``.

    One instance owns the sampling plan (deterministic RNG streams), the
    prefetch pool, and the staging buffers; ``epoch_batches(ep)`` yields
    ready device minibatches in step order.
    """

    def __init__(self, ps: PartitionSet, cfg: GNNConfig, base_seed: int = 0,
                 mesh=None, injector=None):
        self.cfg = cfg
        self.pcfg = cfg.pipeline
        self.plan = SamplingPlan(ps=ps, cfg=cfg, base_seed=base_seed,
                                 injector=injector)
        self.sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self.sharding = NamedSharding(mesh, PartitionSpec("data"))

    @property
    def num_ranks(self) -> int:
        return self.plan.ps.num_parts

    def batches(self, schedule: List[Sequence[np.ndarray]],
                epoch: int) -> Iterator[dict]:
        """Pipeline an explicit ``schedule[step][rank]`` seed schedule."""
        make = lambda step: self.plan.sample_host(epoch, step, schedule[step])
        host_iter = prefetch(make, len(schedule), self.pcfg.num_workers,
                             self.pcfg.prefetch_depth)
        return device_stage(host_iter, sharding=self.sharding)

    def epoch_batches(self, epoch: int) -> Iterator[dict]:
        """Device minibatches for one training epoch (shuffled, padded)."""
        return self.batches(self.plan.epoch_schedule(epoch), epoch)

    def eval_batches(self, num_batches: int, seed: int = 123) -> Iterator[dict]:
        """Deterministic test-set minibatches (one RNG stream per rank)."""
        bs = self.cfg.batch_size
        schedule = []
        per_rank = []
        for r, part in enumerate(self.plan.ps.parts):
            rng = np.random.default_rng([self.plan.base_seed, seed, r])
            test = np.flatnonzero(part.test_mask)
            per_rank.append((test, rng))
        for _ in range(num_batches):
            row = []
            for test, rng in per_rank:
                pick = rng.permutation(len(test))[:bs]
                row.append(test[pick])
            schedule.append(row)
        return self.batches(schedule, epoch=_EVAL_EPOCH_TAG + seed)
