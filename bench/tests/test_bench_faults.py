"""``correct`` comes out false under each cell's own limits when the timed
path is broken underneath the harness, at test size on the CPU: the step
returns the weights it was given, half of each batch is left out (the
mean taken over the rest), the sampler hands over a wrong label, and on
several chips the exchange between chips is left out; and for the
control, the reference in bfloat16 put in the program's place.  The program as it is comes out correct."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from core.check import judge  # noqa: E402
from test_bench_reference import cpu_cell  # noqa: E402

ONE_CHIP = ["none", "frozen_state", "half_batch", "altered_label", "control"]


def _bench():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def _cells():
    """Each benchmark cell at test size: its model, its number of ranks,
    and the faults it can have."""
    b = _bench()
    models = {}
    for c in b["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
            models[c["name"]] = json.load(f)["config"]["model"]
    return {w["name"]: (w["chips"], models[w["config"]],
                        ONE_CHIP + (["no_exchange"] if w["chips"] > 1 else []))
            for w in b["workloads"]}


CELLS = _cells()
CASES = [(c, f) for c, (_, _, faults) in CELLS.items() for f in faults]


def _limits(cell):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        return json.load(f)["limits"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    cache = tmp_path_factory.mktemp("graphs")
    return {c: cpu_cell(r, m, faults, cache)
            for c, (r, m, faults) in CELLS.items()}


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_fails_the_check(readings, cell, fault):
    correct, rows = judge(readings[cell][fault], _limits(cell))
    assert correct is (fault == "none"), rows


def test_exchange_left_out_is_seen(tmp_path):
    """On four ranks the reference's own push holds every HEC line the
    program holds; with the exchange left out the program holds none."""
    got = cpu_cell(4, "graphsage", ["none", "no_exchange"], tmp_path)
    assert got["none"]["push_mismatch"] == 0
    assert got["no_exchange"]["push_mismatch"] == 1.0
