"""Paper Fig. 5 — DistGNN-MB (AEP/HEC) vs DistDGL-like sync baseline.

Reports measured per-epoch wall time for both modes at equal rank count,
measured per-step communication payloads, and the modeled epoch-time ratio
on the target cluster (sync comm blocks; AEP comm overlaps) — the paper's
5.2x at 64 ranks comes from exactly this volume+overlap gap.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import json
import subprocess
import sys

from benchmarks.common import cpu_child_env, emit

_SCRIPT = r"""
import os, sys, json, time
R = int(sys.argv[1]); mode = sys.argv[2]
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
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=32, num_classes=6)
dd = build_dist_data(ps, cfg)
tr = DistTrainer(cfg=cfg, mesh=make_gnn_mesh(R), num_ranks=R, mode=mode)
state = tr.init_state(jax.random.key(0))
step = tr.make_step()
state, _ = tr.train_epochs(ps, dd, state, 1, step_fn=step)
t0 = time.time()
state, hist = tr.train_epochs(ps, dd, state, 2, step_fn=step)
dt = (time.time() - t0) / 2
acc = tr.evaluate(ps, dd, state, num_batches=4)
dims = layer_dims(cfg)
if mode == "aep":
    comm = aep.aep_bytes_per_step(R, cfg.num_layers, cfg.hec.push_limit, dims)
else:
    comm = aep.sync_bytes_per_step(R, cfg.hec.push_limit, cfg.feat_dim)
print("RESULT" + json.dumps({"epoch_s": dt, "acc": acc, "comm": comm}))
"""


def run(r, mode, vertices=6000):
    env = cpu_child_env()
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(r), mode, str(vertices)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(r=4, smoke=False):
    from repro.core.aep import (aep_bytes_per_step, epoch_time_model,
                                sync_bytes_per_step)
    vertices = 6000
    if smoke:
        r, vertices = 2, 1500
    res = {m: run(r, m, vertices) for m in ("aep", "sync")}
    per_step_compute = 2e-3
    m_aep = epoch_time_model(r, 10, per_step_compute, res["aep"]["comm"],
                             overlap=True)
    m_sync = epoch_time_model(r, 10, per_step_compute, res["sync"]["comm"],
                              overlap=False)
    for m in ("aep", "sync"):
        emit(f"fig5_distdgl_compare_{m}_r{r}", res[m]["epoch_s"] * 1e6,
             f"acc={res[m]['acc']:.3f};comm_per_step={res[m]['comm']}")
    emit(f"fig5_modeled_speedup_r{r}", 0.0,
         f"aep_modeled={m_aep:.4f}s;sync_modeled={m_sync:.4f}s;"
         f"speedup={m_sync/m_aep:.2f}x")
    # paper-scale model (64 ranks, papers100M dims: feat 128 / hidden 256,
    # nc=2000, d=1): DistDGL additionally fetches the FULL sampled
    # neighborhood's remote features (~fanout-expanded), which we model as
    # 8x the capped request volume; AEP overlaps, sync blocks.
    R, nc, L, dims = 64, 2000, 3, [128, 256, 256]
    aep_b = aep_bytes_per_step(R, L, nc, dims)
    sync_b = 8 * sync_bytes_per_step(R, nc, 128)
    p_aep = epoch_time_model(R, 19, 2e-3, aep_b, overlap=True)
    p_sync = epoch_time_model(R, 19, 2e-3, sync_b, overlap=False)
    emit("fig5_paper_scale_model_r64", 0.0,
         f"aep_epoch={p_aep:.3f}s;sync_epoch={p_sync:.3f}s;"
         f"speedup={p_sync/p_aep:.2f}x;paper_reports=5.2x")


if __name__ == "__main__":
    main()
