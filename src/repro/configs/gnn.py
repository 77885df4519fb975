"""GNN training configs — the paper's own models and HEC/AEP hyperparameters.

Mirrors Table 2 (GraphSAGE/GAT on OGBN datasets) and §4.4 HEC settings:
cs=1M entries/layer, nc=2000, ls=2, d=1, minibatch 1000, fan-out 5,10,15.
Scaled-down presets are provided for CPU-sized synthetic graphs.
``PipelineConfig`` says only how the one minibatch source (the host
sampler behind ``repro.pipeline.MinibatchPipeline``) overlaps the device
step: prefetch worker count and depth.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class HECConfig:
    """Historical Embedding Cache parameters (paper §3.2 / §4.4), plus the
    PR 5 replicated hot-vertex tier knobs.

    ``hot_size > 0`` replicates the top-K highest-degree halo'd vertices
    on every rank (the heavy communication tail): they leave the pairwise
    push contract and their refreshes — up to ``hot_budget`` owned rows
    per rank per step — ride the SAME fused AEP all_to_all as a broadcast
    segment.  Replicas age with the HEC life-span; a stale replica
    degrades exactly like an HEC miss (dropped from aggregation), so size
    ``hot_budget * life_span`` to cover the hot vertices owned by the
    busiest rank (each rank refreshes only hubs it owns; the trainer
    warns when undersized).  Both 0 (default) disables the tier,
    bit-compatible with the pre-tier trainer."""
    cache_size: int = 1_000_000     # cs: entries per layer
    ways: int = 8                   # set-associativity (TPU adaptation)
    life_span: int = 2              # ls: purge lines older than this
    push_limit: int = 2000          # nc: max solid embeddings pushed per rank pair
    delay: int = 1                  # d: iterations between push and consume
    hot_size: int = 0               # K: replicated hot-tier slots (0 = off)
    hot_budget: int = 0             # hot rows broadcast per rank per step

    def __post_init__(self):
        assert self.cache_size % self.ways == 0
        assert (self.hot_size > 0) == (self.hot_budget > 0), \
            "hot_size and hot_budget must be enabled together"

    @property
    def num_sets(self) -> int:
        return self.cache_size // self.ways


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Asynchronous minibatch pipeline (repro.pipeline) parameters.

    The paper's §3.3 sampler is synchronous thread-parallel; our analogue
    vectorizes the CSR fanout draw on the host and overlaps minibatch
    preparation with the device step (DistDGL/MassiveGNN-style
    prefetching).  Results are bit-identical for any ``num_workers`` —
    each step owns an RNG stream — so worker count is purely a throughput
    knob.

    Defaults are deliberately conservative (one worker, one batch ahead):
    on an accelerator that fully hides sampling behind the device step,
    while on a host-only CPU backend — where sampling threads and XLA
    compute share cores — it stays neutral.  Raise ``num_workers`` /
    ``prefetch_depth`` when the device step is long relative to sampling.
    """
    num_workers: int = 1            # 0 = synchronous inline sampling
    prefetch_depth: int = 1         # minibatches sampled ahead of the step

    def __post_init__(self):
        if self.num_workers < 0:
            raise ValueError(f"num_workers must be >= 0 "
                             f"(0 = synchronous), got {self.num_workers}")
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}")


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    model: str                       # "graphsage" | "gat"
    fanouts: Sequence[int] = (5, 10, 15)   # sampled neighbors per layer (L2..L0)
    hidden_size: int = 256
    num_hidden_layers: int = 2       # => 3 GNN layers total (paper: 3-layer models)
    num_heads: int = 4               # GAT only
    batch_size: int = 1000
    lr: float = 0.003
    dropout: float = 0.5
    aggregator: str = "mean"         # graphsage: mean; gat: gcn
    feat_dim: int = 128
    num_classes: int = 172
    hec: HECConfig = dataclasses.field(default_factory=HECConfig)
    pipeline: PipelineConfig = dataclasses.field(
        default_factory=PipelineConfig)

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers + 1


# Paper-faithful presets (Table 2).
GRAPHSAGE_PAPERS100M = GNNConfig(
    name="graphsage-papers100m", model="graphsage", lr=0.006,  # multi-socket lr
    feat_dim=128, num_classes=172)
GAT_PAPERS100M = GNNConfig(
    name="gat-papers100m", model="gat", lr=0.001, aggregator="gcn",
    feat_dim=128, num_classes=172)
GRAPHSAGE_PRODUCTS = GNNConfig(
    name="graphsage-products", model="graphsage", lr=0.006,
    feat_dim=100, num_classes=47)
GAT_PRODUCTS = GNNConfig(
    name="gat-products", model="gat", lr=0.001, aggregator="gcn",
    feat_dim=100, num_classes=47)


def small_gnn_config(model: str = "graphsage", **over) -> GNNConfig:
    """CPU-sized preset for tests/examples on synthetic graphs."""
    defaults = dict(
        name=f"{model}-small", model=model, fanouts=(5, 5), hidden_size=64,
        num_hidden_layers=1, batch_size=64, feat_dim=32, num_classes=8,
        lr=0.01, dropout=0.1,
        hec=HECConfig(cache_size=4096, ways=4, life_span=2, push_limit=256,
                      delay=1),
    )
    if model == "gat":
        defaults["aggregator"] = "gcn"
    defaults.update(over)
    return GNNConfig(**defaults)
