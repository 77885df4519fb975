"""Paper §4.4 — HEC hit-rate characterization.

The paper reports 71/47/37% hit-rates at layers L0/L1/L2 (cs=1M, ls=2,
nc=2000, d=1, 64 ranks).  We sweep (cache_size, life_span) at our scale and
report per-layer hit rates; the qualitative structure to reproduce is
(a) L0 > deeper layers and (b) hit-rate increases with cs and ls.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import json
import subprocess
import sys

from benchmarks.common import cpu_child_env, emit

_SCRIPT = r"""
import os, sys, json
R = 4
cs, ls = int(sys.argv[1]), int(sys.argv[2])
V = int(sys.argv[3]) if len(sys.argv) > 3 else 6000
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import jax, numpy as np
from repro.cache import hec_occupancy          # the unified cache (PR 4)
from repro.configs.gnn import HECConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data

g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=6,
                    feat_dim=32, seed=0)
ps = partition_graph(g, R, seed=0)
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=32, num_classes=6,
                       hec=HECConfig(cache_size=cs, ways=4, life_span=ls,
                                     push_limit=512, delay=1))
dd = build_dist_data(ps, cfg)
tr = DistTrainer(cfg=cfg, mesh=make_gnn_mesh(R), num_ranks=R, mode="aep")
state = tr.init_state(jax.random.key(0))
state, hist = tr.train_epochs(ps, dd, state, 3)
rates = [hist[-1].get(f"hec_hits_l{l}", 0) /
         max(hist[-1].get(f"hec_halos_l{l}", 1), 1)
         for l in range(cfg.num_layers)]
occ = [float(hec_occupancy(h)) for h in state["hec"]]
print("RESULT" + json.dumps({"rates": rates, "occ": occ}))
"""


def run(cs, ls, vertices=6000):
    env = cpu_child_env()
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(cs), str(ls), str(vertices)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(smoke=False):
    sweep = [(4096, 2)] if smoke else [(4096, 2), (16384, 2), (16384, 4)]
    vertices = 1500 if smoke else 6000
    for cs, ls in sweep:
        r = run(cs, ls, vertices)
        rates = ";".join(f"l{i}={x:.2f}" for i, x in enumerate(r["rates"]))
        occ = ";".join(f"occ{i}={x:.2f}" for i, x in enumerate(r["occ"]))
        emit(f"hec_hitrate_cs{cs}_ls{ls}", 0.0, rates + ";" + occ)


if __name__ == "__main__":
    main()
