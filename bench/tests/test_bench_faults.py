"""``correct`` comes out false under each cell's own limits when the timed
path is broken underneath the harness, at test size on the CPU: the step
returns the weights it was given, half of each batch is left out (the
mean taken over the rest), the sampler hands over a wrong label, and on
several chips the exchange between chips is left out; and for the
control, the reference in bfloat16 put in the program's place.  The
program as it is comes out correct.

The planted faults run at the small test size.  The control runs at the
cell's own layers, fanouts and widths (``cpu_cell.py`` given the cell's
configuration file): the limits it has to fail were set on the chip at
those.  At two layers of 16 the bfloat16 control's ``grad_err`` read
0.0064 to 0.18 over six seeds, under the limit 0.012 on two; at the
cell's own size it read 0.028 to 0.089 over 24 seeds on GraphSAGE and
0.056 to 0.143 over six on GAT, while the program read under 6e-7 and
1.4e-4."""
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
    """Each benchmark cell at test size: its number of ranks, its model,
    its configuration file, and the faults it can have."""
    b = _bench()
    files = {c["name"]: os.path.join(os.path.dirname(BENCH), c["file"])
             for c in b["configs"]}
    out = {}
    for w in b["workloads"]:
        path = files[w["config"]]
        with open(path) as f:
            model = json.load(f)["config"]["model"]
        out[w["name"]] = (w["chips"], model, path, ONE_CHIP
                          + (["no_exchange"] if w["chips"] > 1 else []))
    return out


CELLS = _cells()
CASES = [(c, f) for c, (_, _, _, faults) in CELLS.items() for f in faults]


def _limits(cell):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        return json.load(f)["limits"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    cache = tmp_path_factory.mktemp("graphs")
    out = {}
    for c, (r, m, path, faults) in CELLS.items():
        out[c] = cpu_cell(r, m, [f for f in faults if f != "control"], cache)
        out[c].update(cpu_cell(r, path, ["control"], cache))
    return out


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
