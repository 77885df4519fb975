"""The benchmark's own copy of the program's synthetic graph generator.

Copied from ``repro.graph.synthetic.synthetic_graph`` and
``repro.graph.graph.from_edges`` as they stood when the benchmark was
defined, so that a later change to the program cannot move the graph the
benchmark trains on.  Degrees are lognormal around ``avg_degree`` and
clipped at 8x it; edges stay mostly inside a vertex's community; labels
are the community and features a noisy community prototype.

The graph is the cell's dataset: it depends on the traffic file's
parameters alone (its ``graph_seed`` included), never on ``--seed``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    """Undirected CSR graph (both directions stored), host numpy."""
    indptr: np.ndarray       # [V+1] int64
    indices: np.ndarray      # [E] int32, sorted within each row
    features: np.ndarray     # [V, F] float32
    labels: np.ndarray       # [V] int32
    train_mask: np.ndarray   # [V] bool
    test_mask: np.ndarray    # [V] bool

    @property
    def num_vertices(self) -> int:
        return len(self.indptr) - 1

    @property
    def num_edges(self) -> int:
        return len(self.indices)

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def neighbors(self, v: int) -> np.ndarray:
        return self.indices[self.indptr[v]:self.indptr[v + 1]]

    def has_edges(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """Bool per pair: is (src[i], dst[i]) an edge?  Each row is sorted,
        so a lower-bound binary search runs over all pairs at once."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        lo, end = self.indptr[src], self.indptr[src + 1]
        hi = end.copy()
        last = max(len(self.indices) - 1, 0)
        while True:
            live = lo < hi
            if not live.any():
                break
            mid = (lo + hi) // 2
            less = self.indices[np.minimum(mid, last)] < dst
            lo = np.where(live & less, mid + 1, lo)
            hi = np.where(live & ~less, mid, hi)
        return (lo < end) & (self.indices[np.minimum(lo, last)] == dst)


def from_edges(src, dst, num_vertices, features, labels, train_mask,
               test_mask) -> Graph:
    """Symmetrise, dedupe and sort (src, dst) into CSR."""
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    key = np.unique(src.astype(np.int64) * num_vertices + dst.astype(np.int64))
    src = key // num_vertices
    dst = key % num_vertices
    indptr = np.zeros(num_vertices + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return Graph(indptr=indptr, indices=dst.astype(np.int32),
                 features=features.astype(np.float32),
                 labels=labels.astype(np.int32),
                 train_mask=train_mask.astype(bool),
                 test_mask=test_mask.astype(bool))


def synthetic_graph(num_vertices: int, avg_degree: int, num_classes: int,
                    feat_dim: int, train_frac: float = 0.1,
                    intra_prob: float = 0.8, noise: float = 1.0,
                    seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    V = num_vertices
    comm = rng.integers(0, num_classes, V)

    deg = np.clip(rng.lognormal(np.log(avg_degree), 0.6, V).astype(np.int64),
                  1, max(2 * avg_degree * 4, 16))
    E = int(deg.sum())
    src = np.repeat(np.arange(V, dtype=np.int64), deg)
    same = rng.random(E) < intra_prob
    order = np.argsort(comm, kind="stable")
    comm_sorted = comm[order]
    starts = np.searchsorted(comm_sorted, np.arange(num_classes))
    ends = np.searchsorted(comm_sorted, np.arange(num_classes), side="right")
    dst = rng.integers(0, V, E)
    sc = comm[src]
    lo, hi = starts[sc], ends[sc]
    intra_pick = order[(lo + (rng.random(E) * (hi - lo)).astype(np.int64))
                       .clip(0, V - 1)]
    dst = np.where(same, intra_pick, dst)
    keep = src != dst
    src, dst = src[keep], dst[keep]

    proto = rng.normal(0, 1, (num_classes, feat_dim)).astype(np.float32)
    feats = proto[comm] + rng.normal(0, noise, (V, feat_dim)).astype(np.float32)

    train_mask = np.zeros(V, bool)
    test_mask = np.zeros(V, bool)
    perm = rng.permutation(V)
    n_train = int(train_frac * V)
    n_test = min(V - n_train, max(n_train, 1000))
    train_mask[perm[:n_train]] = True
    test_mask[perm[n_train:n_train + n_test]] = True

    return from_edges(src, dst, V, feats, comm.astype(np.int32),
                      train_mask, test_mask)
