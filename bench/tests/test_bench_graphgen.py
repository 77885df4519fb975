"""The benchmark's copy of the graph generator: deterministic, equal to
the program's generator it was copied from, and its edge lookup."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from core.graphgen import synthetic_graph  # noqa: E402

ARRAYS = ("indptr", "indices", "features", "labels", "train_mask",
          "test_mask")
PARAMS = dict(num_vertices=4000, avg_degree=15, num_classes=172,
              feat_dim=16, train_frac=0.011)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_same_seed_same_graph(seed):
    a = synthetic_graph(seed=seed, **PARAMS)
    b = synthetic_graph(seed=seed, **PARAMS)
    for name in ARRAYS:
        assert np.array_equal(getattr(a, name), getattr(b, name))
    c = synthetic_graph(seed=seed + 1, **PARAMS)
    assert not np.array_equal(a.indices[:100], c.indices[:100])


def test_equal_to_the_program_generator_it_copies():
    from repro.graph.synthetic import synthetic_graph as program
    a = synthetic_graph(seed=3, **PARAMS)
    b = program(seed=3, **PARAMS)
    for name in ARRAYS:
        assert np.array_equal(getattr(a, name), getattr(b, name)), name


def test_degrees_are_clipped_and_symmetric():
    g = synthetic_graph(seed=1, **PARAMS)
    src = np.repeat(np.arange(g.num_vertices), g.degrees())
    assert g.has_edges(g.indices, src).all()          # both directions
    assert int(g.train_mask.sum()) == int(0.011 * 4000)


def test_edge_lookup_matches_a_plain_scan():
    g = synthetic_graph(seed=2, **PARAMS)
    rng = np.random.default_rng(0)
    s = rng.integers(0, g.num_vertices, 5000)
    d = rng.integers(0, g.num_vertices, 5000)
    src = np.repeat(np.arange(g.num_vertices), g.degrees())
    s = np.concatenate([s, src[:2000]])
    d = np.concatenate([d, g.indices[:2000]])
    plain = np.array([v in set(g.neighbors(u).tolist())
                      for u, v in zip(s, d)])
    assert np.array_equal(g.has_edges(s, d), plain)
    assert plain.sum() >= 2000
