"""The benchmark's FLOP count against the figures worked out by hand, for
the two configurations as their files state them."""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from core.flops import capacities, step_flops  # noqa: E402
from core.reference import Model  # noqa: E402

FANOUTS = (5, 10, 15)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["config"]


def test_block_capacities():
    assert capacities(1000, FANOUTS) == [1_056_000, 176_000, 16_000, 1000]


def test_graphsage_step():
    # 2 * (176000*128*256 + 16000*256*256 + 1000*256*172) * 2 matmuls * 3
    hand = 2 * (176000 * 128 * 256 + 16000 * 256 * 256
                + 1000 * 256 * 172) * 2 * 3
    got = step_flops(Model.from_config(_config("graphsage_papers100m")))
    assert got["total"] == hand
    assert got["total"] == pytest.approx(82.3e9, rel=1e-3)
    assert set(got) == {"projection", "total"}


def test_gat_step():
    got = step_flops(Model.from_config(_config("gat_papers100m")))
    hand_proj = 2 * 3 * (1_056_000 * 128 * 1024 + 176_000 * 1024 * 1024
                         + 16_000 * 1024 * 172)
    assert got["projection"] == hand_proj
    assert got["projection"] == pytest.approx(1.955e12, rel=1e-3)
    assert got["aggregation"] == pytest.approx(6.4e9, rel=0.01)
    assert got["scores"] == 2 * 3 * 2 * (1_056_000 * 1024 + 176_000 * 1024
                                         + 16_000 * 172)
    assert got["total"] == (got["projection"] + got["scores"]
                            + got["aggregation"])
    assert got["total"] == pytest.approx(1.976e12, rel=1e-3)


def test_unknown_model_is_an_error():
    """A model with no file under bench/models/ is refused, by that file's
    name, when its configuration is read and when its FLOP are counted."""
    c = dict(_config("graphsage_papers100m"), model="gin")
    want = os.path.join(BENCH, "models", "gin.py")
    with pytest.raises(FileNotFoundError) as e:
        Model.from_config(c)
    assert want in str(e.value)
    m = Model(model="gin", feat_dim=128, hidden=256, num_classes=172,
              num_layers=3, num_heads=1, fanouts=FANOUTS, batch_size=1000,
              dropout=0.5, lr=1e-3)
    with pytest.raises(FileNotFoundError) as e:
        step_flops(m)
    assert want in str(e.value)
