"""The plain reference against the program's timed path at test size on
the CPU: one rank (GraphSAGE, GAT) and four virtual devices (GraphSAGE,
with the AEP push).  On the CPU float32 matrix products are exact to
rounding, so every gap reads under 1e-5; the minibatches break no
invariant; on four ranks the HEC lines the program holds are the
reference's own pushes."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def cpu_cell(ranks, model, faults, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "cpu_cell.py"),
                        str(ranks), model, ",".join(faults), str(cache)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("ranks,model", [(1, "graphsage"), (1, "gat"),
                                         (4, "graphsage")])
def test_reference_follows_the_timed_path(ranks, model, tmp_path):
    got = cpu_cell(ranks, model, ["none"], tmp_path)["none"]
    assert got["batch_faults"] == 0
    for name in ("loss_gap", "grad_gap", "grad_err", "change_gap"):
        assert got[name] < 1e-5, (name, got)
    if ranks > 1:
        assert got["push_mismatch"] == 0 and got["push_gap"] < 1e-5
    else:
        assert "push_mismatch" not in got
