"""GNN training configs — the paper's own models and HEC/AEP hyperparameters.

Mirrors Table 2 (GraphSAGE/GAT on OGBN datasets) and §4.4 HEC settings:
cs=1M entries/layer, nc=2000, ls=2, d=1, minibatch 1000, fan-out 5,10,15.
Scaled-down presets are provided for CPU-sized synthetic graphs.
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
class SamplerConfig:
    """Fanout-draw policy and placement (host numpy vs on-device kernel).

    ``device_draw=False`` (default) keeps the host vectorized sampler —
    byte-identical to every prior release and the fallback for host-only
    backends.  ``device_draw=True`` moves the per-layer neighbor draw
    onto the device (``kernels/sample_draw.py``): deterministic per
    (base_seed, epoch, step, rank, layer) via ``jax.random`` fold_in
    chaining, hence bit-reproducible for any prefetch worker count.

    Policies (device draw only — the host loop stays uniform):
      uniform  iid neighbor sampling (NS; the paper's sampler)
      labor    LABOR-style correlated draw: one shared hash key per
               *vertex*, so overlapping fanouts select the same
               neighbors and the minibatch frontier shrinks
      cv       control-variate sampling (arxiv 1710.10568): LABOR keys
               divided by ``1 + cv_boost * resident``, preferring
               vertices whose historical activations sit in the HEC —
               the trainer refreshes residency from the live cache tags
               each epoch
    """
    policy: str = "uniform"         # uniform | labor | cv
    device_draw: bool = False       # on-device kernel draw (host np default)
    cv_boost: float = 4.0           # cv: weight boost for HEC-resident rows
    use_kernel: bool = True         # Pallas keys kernel (False = jnp ref)

    def __post_init__(self):
        if self.policy not in ("uniform", "labor", "cv"):
            raise ValueError(f"policy must be uniform|labor|cv, "
                             f"got {self.policy!r}")
        if self.policy != "uniform" and not self.device_draw:
            raise ValueError(
                f"policy={self.policy!r} needs device_draw=True "
                f"(the host fallback draw is uniform-only)")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Asynchronous minibatch pipeline (repro.pipeline) parameters.

    The paper's §3.3 sampler is synchronous thread-parallel; our analogue
    vectorizes the CSR fanout draw and overlaps minibatch preparation with
    the device step (DistDGL/MassiveGNN-style prefetching).  Results are
    bit-identical for any ``num_workers`` — each step owns an RNG stream —
    so worker count is purely a throughput knob.

    Defaults are deliberately conservative (one worker, one batch ahead):
    on an accelerator that fully hides sampling behind the device step,
    while on a host-only CPU backend — where sampling threads and XLA
    compute share cores — it stays neutral.  Raise ``num_workers`` /
    ``prefetch_depth`` when the device step is long relative to sampling.
    """
    enabled: bool = True            # default training path uses the pipeline
    num_workers: int = 1            # 0 = synchronous inline sampling
    prefetch_depth: int = 1         # minibatches sampled ahead of the step
    double_buffer: bool = True      # overlap device_put(k+1) with step k
    vectorized: bool = True         # vectorized CSR sampler (vs reference)
    sampler: SamplerConfig = dataclasses.field(
        default_factory=SamplerConfig)

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
