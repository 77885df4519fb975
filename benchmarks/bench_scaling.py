"""Paper Figs. 3 & 4 — epoch time / speedup vs ranks (GraphSAGE & GAT).

This container has ONE physical core, so multi-rank wall-clock does not
show real scaling (R host devices time-share a core).  We therefore report
(a) measured per-epoch wall time, (b) measured per-rank step count and
per-step communication payload, and (c) a modeled epoch time on the target
cluster (per-rank compute scaled 1/R, AEP comm overlapped, ARed blocking)
mirroring the paper's epoch-time decomposition MBC+FWD+BWD+ARed.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import subprocess
import sys
import json

from benchmarks.common import cpu_child_env, emit

_SCRIPT = r"""
import os, sys, json, time
R = int(sys.argv[1]); model = sys.argv[2]
V = int(sys.argv[3]) if len(sys.argv) > 3 else 6000
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import jax, numpy as np
from repro.configs.gnn import small_gnn_config
from repro.core import aep
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data, layer_dims

g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=6,
                    feat_dim=32, seed=0)
ps = partition_graph(g, R, seed=0)
cfg = small_gnn_config(model, batch_size=64, feat_dim=32, num_classes=6)
dd = build_dist_data(ps, cfg)
tr = DistTrainer(cfg=cfg, mesh=make_gnn_mesh(R), num_ranks=R, mode="aep")
state = tr.init_state(jax.random.key(0))
step = tr.make_step()
state, _ = tr.train_epochs(ps, dd, state, 1, step_fn=step)   # warm/compile
t0 = time.time()
state, hist = tr.train_epochs(ps, dd, state, 2, step_fn=step)
dt = (time.time() - t0) / 2
steps = int(np.ceil(max(ps.parts[r].train_mask.sum() for r in range(R))
                    / cfg.batch_size))
dims = layer_dims(cfg)
comm = aep.aep_bytes_per_step(R, cfg.num_layers, cfg.hec.push_limit, dims)
print("RESULT" + json.dumps({"epoch_s": dt, "steps": steps,
                             "comm_bytes_per_step": comm,
                             "acc": hist[-1]["acc"]}))
"""


def run_rank(r, model, vertices=6000):
    env = cpu_child_env()
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(r), model, str(vertices)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(ranks=(1, 2, 4), models=("graphsage", "gat"), smoke=False):
    from repro.core.aep import epoch_time_model
    vertices = 6000
    if smoke:
        ranks, models, vertices = (1, 2), ("graphsage",), 1500
    for model in models:
        base = None
        for r in ranks:
            res = run_rank(r, model, vertices)
            # modeled target-cluster epoch time: compute scales ~1/R via
            # fewer minibatches/rank; AEP comm overlaps (paper: hidden at d=1)
            per_step_compute = 2e-3        # nominal target per-mb fwd+bwd (s)
            modeled = epoch_time_model(r, res["steps"], per_step_compute,
                                       res["comm_bytes_per_step"],
                                       overlap=True)
            if base is None:
                base = modeled
            fig = "fig3" if model == "graphsage" else "fig4"
            emit(f"{fig}_scaling_{model}_r{r}", res["epoch_s"] * 1e6,
                 f"steps={res['steps']};comm_per_step={res['comm_bytes_per_step']};"
                 f"modeled_epoch_s={modeled:.4f};modeled_speedup={base/modeled:.2f}x")


if __name__ == "__main__":
    main()
