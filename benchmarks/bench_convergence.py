"""Paper Table 3 / §4.5 — convergence parity.

Single-rank training establishes the target accuracy; distributed training
must reach within 1% of it (the paper's protocol: distributed takes more
epochs but converges to parity).  Reports epochs-to-target for 1 vs 4 ranks.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import json
import subprocess
import sys

from benchmarks import common
from benchmarks.common import cpu_child_env, emit

_SCRIPT = r"""
import os, sys, json
R = int(sys.argv[1])
EP = int(sys.argv[2]) if len(sys.argv) > 2 else 10
V = int(sys.argv[3]) if len(sys.argv) > 3 else 6000
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import jax, numpy as np
from repro import obs
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data

obs.configure(obs.ObsConfig())
g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=6,
                    feat_dim=32, seed=0)
ps = partition_graph(g, R, seed=0)
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=32, num_classes=6)
dd = build_dist_data(ps, cfg)
# quality plane: the per-epoch loss/train-acc/grad-norm series flows into
# the registry event log; eval accuracy joins it as "eval" events, and the
# RESULT series is read back OUT of the event log (one sink, one ordering)
quality = obs.QualityPlane()
tr = DistTrainer(cfg=cfg, mesh=make_gnn_mesh(R), num_ranks=R, mode="aep",
                 quality=quality)
state = tr.init_state(jax.random.key(0))
step = tr.make_step()
reg = obs.get().registry
for ep in range(EP):
    state, hist = tr.train_epochs(ps, dd, state, 1, step_fn=step)
    reg.log_event("eval", epoch=ep,
                  acc=float(tr.evaluate(ps, dd, state, num_batches=4)))
accs = [ev["acc"] for ev in reg.events_of("eval")]
losses = [ev["loss"] for ev in reg.events_of("convergence") if "loss" in ev]
print("RESULT" + json.dumps({"accs": accs, "losses": losses}))
"""


def run(r, epochs=10, vertices=6000):
    env = cpu_child_env()
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(r), str(epochs), str(vertices)],
        env=env, capture_output=True, text=True, timeout=1800)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(smoke=False):
    if smoke:
        r = run(1, epochs=2, vertices=1500)
        accs = r["accs"]
        for i, a in enumerate(accs):
            emit(f"table3_acc_ep{i}", 0.0, f"acc={a:.3f}")
        emit("table3_convergence_smoke", 0.0,
             f"best_acc={max(accs):.3f};epochs={len(accs)}")
        common.result({"accs": accs, "losses": r["losses"]})
        return
    single = run(1)["accs"]
    target = max(single)
    r4 = run(4)
    dist = r4["accs"]
    for i, a in enumerate(dist):
        emit(f"table3_acc_ep{i}", 0.0, f"acc_4rank={a:.3f}")

    def epochs_to(accs, tgt):
        for i, a in enumerate(accs):
            if a >= tgt - 0.01:            # within 1% of target (paper)
                return i + 1
        return -1

    emit("table3_convergence_1rank", 0.0,
         f"target_acc={target:.3f};epochs_to_target={epochs_to(single, target)}")
    emit("table3_convergence_4rank", 0.0,
         f"best_acc={max(dist):.3f};epochs_to_target={epochs_to(dist, target)};"
         f"parity={'yes' if max(dist) >= target - 0.01 else 'no'}")
    common.result({"single_accs": single, "dist_accs": dist,
                   "dist_losses": r4["losses"], "target_acc": target})


if __name__ == "__main__":
    main()
