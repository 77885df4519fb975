"""Staleness sweep — embedding quality vs HEC life-span (quality plane).

The paper's bounded-staleness argument (§3.2): a larger life-span keeps
more historical embeddings alive — cheaper epochs, staler aggregation
inputs.  This suite makes the trade measurable: train the same graph at
life-span ∈ {1, 4, 16, ∞} and record, per point, the epoch time, the
final test accuracy, and the quality plane's audit error (mean relative
L2 of cached hidden-layer embeddings vs the exact full-graph recompute).

Artifact schema (``BENCH_quality.json``, consumed by the docs plots):

  rows:   one ``quality_ls<span>`` row per sweep point, ``us_per_call``
          = steady-state epoch seconds * 1e6 (the sentinel's timing
          surface), derived = ``acc=..;audit_err=..;stale_age_mean=..``
  result: ``{"sweep": [{"life_span", "epoch_s", "acc", "audit_err",
          "mean_err", "stale_age_mean"}, ...]}`` in sweep order
          (life_span ∞ is recorded as 10**9)

Gates (even at smoke scale): ``stale_age_mean`` is nondecreasing in
life-span (the purge bound is real), and the audit error at life-span ∞
is no better than at life-span 1 beyond noise (staleness never helps).
Runs each point in a subprocess so every sweep sets its own device count
before jax imports — and uses >= 2 ranks: a single-rank partition has no
halo pushes, so its training HECs stay empty and the audit (correctly)
reports no signal.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import json
import subprocess
import sys

from benchmarks import common

_SCRIPT = r"""
import os, sys, json, time
LS = int(sys.argv[1]); EP = int(sys.argv[2])
V = int(sys.argv[3]); R = int(sys.argv[4])
if LS < 0:
    LS = 10**9                      # "infinite": never purge
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import jax, numpy as np
from repro import obs
from repro.configs.gnn import HECConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.obs.quality import valid_ages
from repro.train.gnn_trainer import DistTrainer, build_dist_data

obs.configure(obs.ObsConfig())
g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=6,
                    feat_dim=32, seed=0)
ps = partition_graph(g, R, seed=0)
# dropout 0 so the audit error is staleness drift + sampled-neighborhood
# approximation only; lr high enough that params move between refreshes
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=32,
                       num_classes=6, lr=0.05, dropout=0.0,
                       hec=HECConfig(cache_size=8192, ways=4, life_span=LS,
                                     push_limit=512, delay=1))
dd = build_dist_data(ps, cfg)
quality = obs.QualityPlane(obs.QualityConfig(audit_samples=512))
tr = DistTrainer(cfg=cfg, mesh=make_gnn_mesh(R), num_ranks=R, mode="aep",
                 quality=quality)
state = tr.init_state(jax.random.key(0))
step = tr.make_step()
state, _ = tr.train_epochs(ps, dd, state, 1, step_fn=step)  # compile epoch
t0 = time.perf_counter()
state, _ = tr.train_epochs(ps, dd, state, EP, step_fn=step)
epoch_s = (time.perf_counter() - t0) / EP
acc = tr.evaluate(ps, dd, state, num_batches=4)
rep = tr.audit(ps, dd, state, epoch=EP)
hidden = [valid_ages(st) for st in state["hec"][1:]]
ages = np.concatenate(hidden) if hidden else np.zeros(0)
print("RESULT" + json.dumps({
    "life_span": LS, "epoch_s": epoch_s, "acc": float(acc),
    "audit_err": rep.hidden_mean_err(), "mean_err": rep.mean_err,
    "stale_age_mean": float(ages.mean()) if ages.size else None}))
"""

# -1 encodes "infinite" (no purge); kept last so the sweep is ordered by
# effective staleness bound
SPANS = [1, 4, 16, -1]


def run(ls, epochs, vertices, ranks):
    env = common.cpu_child_env()
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(ls), str(epochs),
         str(vertices), str(ranks)],
        env=env, capture_output=True, text=True, timeout=1800)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(smoke=False):
    epochs, vertices, ranks = (3, 1200, 2) if smoke else (8, 6000, 4)
    fmt = lambda v, spec=".4f": "n/a" if v is None else f"{v:{spec}}"
    sweep = []
    for ls in SPANS:
        r = run(ls, epochs, vertices, ranks)
        label = "inf" if ls < 0 else str(ls)
        common.emit(
            f"quality_ls{label}", r["epoch_s"] * 1e6,
            f"acc={r['acc']:.3f};audit_err={fmt(r['audit_err'])};"
            f"stale_age_mean={fmt(r['stale_age_mean'], '.2f')}")
        sweep.append(r)

    # gate 1: the purge bound is real — mean valid age never decreases as
    # the life-span grows (equal is fine: short runs can't age past a
    # large bound)
    ages = [p["stale_age_mean"] for p in sweep]
    assert all(a is not None for a in ages), \
        f"audit found no cached hidden-layer entries: {ages}"
    for lo, hi in zip(ages, ages[1:]):
        assert hi >= lo - 1e-9, f"stale age not monotone: {ages}"
    # gate 2: staleness never helps — unbounded life-span audits no
    # better than life-span 1 (small tolerance: the audit samples lines)
    errs = [p["audit_err"] for p in sweep]
    if errs[0] is not None and errs[-1] is not None:
        assert errs[-1] >= errs[0] - 0.02, \
            f"audit error improved with staleness: {errs}"
    common.result({"sweep": sweep})


if __name__ == "__main__":
    main()
