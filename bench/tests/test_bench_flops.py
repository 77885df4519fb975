"""The benchmark's FLOP count against the figures worked out by hand."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core.flops import capacities, step_flops  # noqa: E402

FANOUTS = (5, 10, 15)


def test_block_capacities():
    assert capacities(1000, FANOUTS) == [1_056_000, 176_000, 16_000, 1000]


def test_graphsage_step():
    # 2 * (176000*128*256 + 16000*256*256 + 1000*256*172) * 2 matmuls * 3
    hand = 2 * (176000 * 128 * 256 + 16000 * 256 * 256
                + 1000 * 256 * 172) * 2 * 3
    got = step_flops("graphsage", 1000, FANOUTS, 128, 256, 172)
    assert got["total"] == hand
    assert got["total"] == pytest.approx(82.3e9, rel=1e-3)
    assert got["scores"] == got["aggregation"] == 0


def test_gat_step():
    got = step_flops("gat", 1000, FANOUTS, 128, 256, 172, num_heads=4)
    hand_proj = 2 * 3 * (1_056_000 * 128 * 1024 + 176_000 * 1024 * 1024
                         + 16_000 * 1024 * 172)
    assert got["projection"] == hand_proj
    assert got["projection"] == pytest.approx(1.955e12, rel=1e-3)
    assert got["aggregation"] == pytest.approx(6.4e9, rel=0.01)
    assert got["scores"] == 2 * 3 * 2 * (1_056_000 * 1024 + 176_000 * 1024
                                         + 16_000 * 172)
    assert got["total"] == (got["projection"] + got["scores"]
                            + got["aggregation"])


def test_unknown_model_is_an_error():
    with pytest.raises(ValueError):
        step_flops("gin", 1000, FANOUTS, 128, 256, 172)
