"""A benchmark run at test size on the CPU, with a fault planted on demand.

    python bench/tests/cpu_cell.py <ranks> <model> <faults> <cache_dir> [seed]

Skips the harness's look for a chip and drives the rest of a run: the
graph and partition through the cache, ``setup_gnn``, the warm-up epoch
with the first three steps recorded, a short window, and the check
against the reference.  ``model`` is ``graphsage`` or ``gat`` at the
small test size (two layers of 16, fanouts 3 and 4, on a 3,000-vertex
graph), or a configuration file (``bench/configs/<name>.json``) whose
model runs at the file's own layers, fanouts, widths, learning rate and
dropout, on a 6,000-vertex graph; batch 32 and a test-sized HEC either
way.  ``seed`` defaults to 2**31 + 11.  Each of the comma-separated
``faults`` breaks the timed path underneath the harness for one run (all
runs share one process and one compiled step):

  none          the program as it is
  frozen_state  the step returns the weights it was given
  half_batch    the step sees only the first half of each seed batch
  no_exchange   the AEP push never lands (each step gets an empty queue)
  altered_label the sampler hands the step a wrong label
  control       the reference in bfloat16 in the program's place

Prints one JSON line: per fault, the compared numbers.
"""
from __future__ import annotations

import json
import os
import sys
import time


def main():
    ranks, model, faults, cache = (int(sys.argv[1]), sys.argv[2],
                                   sys.argv[3].split(","), sys.argv[4])
    seed = int(sys.argv[5]) if len(sys.argv) > 5 else 2**31 + 11
    state = {"fault": None}
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ranks}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.dirname(here)
    sys.path.insert(0, bench)
    sys.path.insert(1, os.path.join(os.path.dirname(bench), "src"))

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from core import check, session
    from core.device import CompileClock
    from core.reference import Model
    from repro.configs.gnn import HECConfig, small_gnn_config
    from repro.pipeline.prefetcher import SamplingPlan
    from repro.train.gnn_trainer import DistTrainer

    traffic = dict(num_vertices=3000, avg_degree=8, num_classes=8,
                   feat_dim=32, train_frac=0.1, graph_seed=0, parts=ranks)
    sizes = dict(fanouts=(3, 4), hidden_size=16, num_heads=2, dropout=0.5)
    if model.endswith(".json"):
        with open(model) as f:
            c = json.load(f)["config"]
        model = c["model"]
        traffic.update(num_vertices=6000, num_classes=c["num_classes"],
                       feat_dim=c["feat_dim"])
        sizes = {k: c[k] for k in ("hidden_size", "num_hidden_layers",
                                   "num_heads", "lr", "dropout")}
        sizes["fanouts"] = tuple(c["fanouts"])
    cfg = small_gnn_config(
        model, batch_size=32, feat_dim=traffic["feat_dim"],
        num_classes=traffic["num_classes"],
        hec=HECConfig(cache_size=65536, ways=8, life_span=2, push_limit=64,
                      delay=1), **sizes)

    make_step = DistTrainer.make_step

    def broken(self, *a, **k):
        real = make_step(self, *a, **k)

        def step(params, opt, hec, hot, inflight, data, mb, seed, *rest):
            if state["fault"] == "half_batch":
                B = mb["seed_mask"].shape[1]
                mb = dict(mb, seed_mask=mb["seed_mask"]
                          & (jnp.arange(B) < B // 2)[None])
            if state["fault"] == "no_exchange":
                inflight = jax.tree_util.tree_map(
                    lambda a: jnp.full_like(a, -1) if a.dtype == jnp.int32
                    else jnp.zeros_like(a), inflight)
            out = real(params, opt, hec, hot, inflight, data, mb, seed, *rest)
            if state["fault"] == "frozen_state":
                out = (params,) + tuple(out[1:])
            return out
        return step

    DistTrainer.make_step = broken
    sample_host = SamplingPlan.sample_host

    def altered(self, *a, **k):
        out = sample_host(self, *a, **k)
        if state["fault"] == "altered_label":
            out["labels"][:, 0] = (out["labels"][:, 0] + 1) % cfg.num_classes
        return out
    SamplingPlan.sample_host = altered

    g = session.load_graph(traffic, cache)
    ps = session.load_partition(g, traffic, cache)
    c = dataclasses.asdict(cfg)
    c["fanouts"] = list(c["fanouts"])
    out = {}
    for fault in faults:
        state["fault"] = fault
        run, rec = session.run_cell(cfg, g, ps, ranks, seed, 0.3,
                                    time.perf_counter(), CompileClock())
        assert run.epochs >= 1 and np.isfinite(run.window_s)
        chk = check.Check(rec, ps, g, Model.from_config(c), c["hec"], seed)
        out[fault] = chk.control() if fault == "control" else chk.program()
    print(json.dumps(out))

if __name__ == "__main__":
    main()
