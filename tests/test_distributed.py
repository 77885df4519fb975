"""Multi-rank integration tests.

These need multiple XLA host devices, which must be forced BEFORE jax
initializes — so the actual work runs in a subprocess with XLA_FLAGS set.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data

g = synthetic_graph(num_vertices=3000, avg_degree=8, num_classes=6,
                    feat_dim=24, seed=0)
ps = partition_graph(g, 4, seed=0)
mesh = make_gnn_mesh(4)
out = {}
for mode in ["aep", "sync", "drop"]:
    cfg = small_gnn_config("graphsage", batch_size=32, feat_dim=24,
                           num_classes=6)
    dd = build_dist_data(ps, cfg)
    tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=4, mode=mode)
    state = tr.init_state(jax.random.key(0))
    state, hist = tr.train_epochs(ps, dd, state, 4)
    acc = tr.evaluate(ps, dd, state, num_batches=4)
    rates = {}
    for l in range(cfg.num_layers):
        h = hist[-1].get(f"hec_hits_l{l}", 0.0)
        t = hist[-1].get(f"hec_halos_l{l}", 1.0)
        rates[l] = h / max(t, 1.0)
    out[mode] = {"loss0": hist[0]["loss"], "loss": hist[-1]["loss"],
                 "acc": acc, "hit_rates": rates}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_aep_converges_distributed(results):
    r = results["aep"]
    assert r["loss"] < r["loss0"] * 0.5
    assert r["acc"] > 0.7


def test_hec_hit_rates_layered(results):
    """Hit-rates positive and (paper §4.4) higher at layer 0 than deeper."""
    rates = results["aep"]["hit_rates"]
    assert rates["0"] > 0.1
    assert rates["0"] >= rates["1"] * 0.8


def test_sync_baseline_converges(results):
    assert results["sync"]["acc"] > 0.7


def test_aep_not_worse_than_drop(results):
    """HEC embeddings help vs ignoring cut edges (accuracy parity claim)."""
    assert results["aep"]["acc"] >= results["drop"]["acc"] - 0.05


_PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data

g = synthetic_graph(num_vertices=2000, avg_degree=8, num_classes=6,
                    feat_dim=24, seed=0)
ps = partition_graph(g, 4, seed=0)
mesh = make_gnn_mesh(4)
cfg = small_gnn_config("graphsage", batch_size=32, feat_dim=24,
                       num_classes=6)
dd = build_dist_data(ps, cfg, mesh)
tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=4)
state = tr.init_state(jax.random.key(0), dd)


def placement(tree):
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        sh = x.sharding
        out[jax.tree_util.keystr(path)] = {
            "spec": str(sh.spec) if isinstance(sh, NamedSharding) else None,
            "devices": sorted(s.device.id for s in x.addressable_shards),
            "rows": sorted({s.data.shape[0] if s.data.ndim else 0
                            for s in x.addressable_shards}),
        }
    return out


print("RESULT" + json.dumps({
    "data": placement(dd),
    "rank_state": placement({k: state[k] for k in ("hec", "inflight")}),
    "replicated": placement({k: state[k] for k in ("params", "opt_state")}),
}))
"""


def test_rank_state_is_sharded_on_data_when_created():
    """build_dist_data(mesh=...) and init_state place every [R, ...] array
    with one rank's slice per device, and the replicated params on every
    device, without staging the stack on one device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    res = json.loads(line[len("RESULT"):])
    for group in ("data", "rank_state"):
        assert res[group]
        for name, p in res[group].items():
            assert p == {"spec": "PartitionSpec('data',)",
                         "devices": [0, 1, 2, 3], "rows": [1]}, (name, p)
    for name, p in res["replicated"].items():
        assert p["spec"] == "PartitionSpec()", (name, p)
        assert p["devices"] == [0, 1, 2, 3], (name, p)
