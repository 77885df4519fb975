"""Resilience plane benchmark (repro.resilience subsystem).

Three costs the resilience plane is allowed to charge, measured:

  * **checkpoint save/restore** — one atomic ``.npz`` of the FULL
    distributed train state (params, opt state, every layer's HEC, hot
    tier, inflight push queue).  Save must stay a small fraction of an
    epoch (it runs at every epoch boundary when armed); restore is paid
    once per crash.  A digest roundtrip gates correctness even at smoke
    scale,
  * **degraded-vs-healthy serve throughput** — the same query stream
    pumped through a 4-shard ``DistGNNServeScheduler`` with every rank
    alive vs one rank breaker-open: degraded mode answers from stale
    replicas / bounded drops instead of stalling, and this row prices
    that bypass,
  * **recovery time** — rounds (and wall time) from arming a passing
    re-probe until the breaker closes and ``serve_degraded`` drops back
    to zero.

Runs in subprocesses so each piece gets its own XLA device count.  Emits
``name,us_per_call,derived`` CSV rows plus one ``RESULT{...}`` line.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import json
import subprocess
import sys
import tempfile

from benchmarks.common import cpu_child_env, emit, result

_CKPT_SCRIPT = r"""
import os, sys, json, time
R = int(sys.argv[1]); V = int(sys.argv[2]); E = int(sys.argv[3])
work = sys.argv[4]
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import hashlib
import jax, numpy as np
from repro import resilience
from repro.configs.gnn import HECConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data

g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=16,
                    feat_dim=64, seed=0)
ps = partition_graph(g, R, seed=0)
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=64,
                       num_classes=16, fanouts=(5, 10), hidden_size=128,
                       hec=HECConfig(cache_size=16384, ways=8, life_span=2,
                                     push_limit=512, delay=1))
dd = build_dist_data(ps, cfg)
mesh = make_gnn_mesh(R)
rz = resilience.ResiliencePlane(resilience.ResilienceConfig(
    ckpt_dir=os.path.join(work, "ck"), ckpt_keep=2))
tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=R, mode="aep",
                 resilience=rz)
state = tr.init_state(jax.random.key(0))
t0 = time.perf_counter()
state, _ = tr.train_epochs(ps, dd, state, E, log_every=0)
epoch_s = (time.perf_counter() - t0) / E

reps = 3
t0 = time.perf_counter()
for i in range(reps):
    rz.ckpt.save(state, 100 + i)
t_save = (time.perf_counter() - t0) / reps
size = os.path.getsize(rz.ckpt.path_for(100 + reps - 1))
t0 = time.perf_counter()
for _ in range(reps):
    restored, _ = rz.ckpt.restore(state)
t_restore = (time.perf_counter() - t0) / reps

dg = lambda s: hashlib.sha256(
    b"".join(np.asarray(l).tobytes()
             for l in jax.tree_util.tree_leaves(s))).hexdigest()
print("RESULT" + json.dumps({
    "t_save": t_save, "t_restore": t_restore, "bytes": size,
    "epoch_s": epoch_s, "roundtrip": dg(restored) == dg(state)}))
"""

_SERVE_SCRIPT = r"""
import os, sys, json, time
V = int(sys.argv[1]); Q = int(sys.argv[2])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.cache import ServeCacheConfig
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.serve.gnn.distributed import (DistGNNServeScheduler,
                                         DistServeConfig,
                                         layerwise_embeddings_dist)
from repro.train.gnn_trainer import init_model_params

R = 4
g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=16,
                    feat_dim=64, seed=0, intra_prob=0.5)
ps = partition_graph(g, R, seed=0)
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=64,
                       num_classes=16, fanouts=(5, 10), hidden_size=128)
params = init_model_params(jax.random.key(0), cfg)
srv = DistGNNServeScheduler(
    cfg, params, ps, make_gnn_mesh(R),
    DistServeConfig(num_slots=16, halo_slots=256,
                    cache=ServeCacheConfig(cache_size=32768, ways=8),
                    hot_size=V // 8, failover=True))
embs = layerwise_embeddings_dist(cfg, params, ps, chunk_size=2048)
srv.cache.warm(embs, np.arange(V), layers=range(cfg.num_layers - 1))
srv.hot.warm(embs)
rng = np.random.default_rng(0)
srv.serve(rng.integers(0, V, 64))              # compile outside timings

def pump_qps(qs):
    t0 = time.perf_counter()
    srv.serve(qs)
    return len(qs) / (time.perf_counter() - t0)

healthy_qps = pump_qps(rng.integers(0, V, Q))
srv.probe_fn = lambda r: False                 # re-probes keep failing
srv.mark_dead(1)
degraded_qps = pump_qps(rng.integers(0, V, Q))
m = srv.metrics()

# recovery: rounds + wall time from arming a passing probe until the
# breaker closes (each serve call pumps >= 1 round; bounded loop)
srv.probe_fn = lambda r: True
rounds0 = srv.steps_run
t0 = time.perf_counter()
for _ in range(10):
    if not srv.breaker.any_dead:
        break
    srv.serve(rng.integers(0, V, 16))
t_rec = time.perf_counter() - t0
print("RESULT" + json.dumps({
    "healthy_qps": healthy_qps, "degraded_qps": degraded_qps,
    "degraded_answers": m["degraded_answers"],
    "degraded_dropped": m["degraded_dropped"],
    "recovery_rounds": srv.steps_run - rounds0, "t_rec": t_rec,
    "recovered": not srv.breaker.any_dead,
    "post_degraded": srv.metrics()["serve_degraded"]}))
"""


def _run(script, *argv):
    env = cpu_child_env()
    out = subprocess.run(
        [sys.executable, "-c", script, *[str(a) for a in argv]],
        capture_output=True, text=True, env=env, check=False)
    if out.returncode != 0:
        raise RuntimeError(f"bench_resilience child failed:\n"
                           f"{out.stderr[-4000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(smoke=False):
    V = 1500 if smoke else 8000
    Q = 256 if smoke else 1024
    with tempfile.TemporaryDirectory() as work:
        ck = _run(_CKPT_SCRIPT, 2, V, 1, work)
    sv = _run(_SERVE_SCRIPT, V, Q)

    save_frac = ck["t_save"] / max(ck["epoch_s"], 1e-9)
    emit("resilience_ckpt_save", ck["t_save"] * 1e6,
         f"bytes={ck['bytes']};mb={ck['bytes']/1e6:.1f};"
         f"epoch_s={ck['epoch_s']:.2f};save_frac={save_frac:.3f}")
    emit("resilience_ckpt_restore", ck["t_restore"] * 1e6,
         f"roundtrip_exact={ck['roundtrip']}")
    ratio = sv["degraded_qps"] / max(sv["healthy_qps"], 1e-9)
    emit("resilience_degraded_serve", 1e6 / max(sv["degraded_qps"], 1e-9),
         f"healthy_qps={sv['healthy_qps']:.0f};"
         f"degraded_qps={sv['degraded_qps']:.0f};ratio={ratio:.2f};"
         f"replica_answers={sv['degraded_answers']};"
         f"dropped={sv['degraded_dropped']}")
    emit("resilience_recovery", sv["t_rec"] * 1e6,
         f"rounds={sv['recovery_rounds']};"
         f"post_degraded={sv['post_degraded']}")

    # CORRECTNESS GATES (run in --smoke too): the checkpoint roundtrip is
    # bit-exact, degraded mode really served the dead rank's queries, and
    # the breaker actually closed after the passing re-probe
    assert ck["roundtrip"], "checkpoint save/restore must be bit-exact"
    assert sv["degraded_answers"] + sv["degraded_dropped"] > 0, \
        "the dead rank's queries never hit the degraded path"
    assert sv["recovered"] and sv["post_degraded"] == 0.0, \
        "breaker must close after a passing re-probe"
    if not smoke:       # wall-clock bars don't gate the tiny-scale CI pass
        assert save_frac < 0.2, \
            f"epoch-boundary checkpointing must cost < 20% of an epoch, " \
            f"got {save_frac:.2f}"
    result({
        "ckpt_save_us": ck["t_save"] * 1e6,
        "ckpt_restore_us": ck["t_restore"] * 1e6,
        "ckpt_bytes": ck["bytes"], "ckpt_save_frac": save_frac,
        "healthy_qps": sv["healthy_qps"],
        "degraded_qps": sv["degraded_qps"],
        "degraded_ratio": ratio,
        "degraded_answers": sv["degraded_answers"],
        "degraded_dropped": sv["degraded_dropped"],
        "recovery_rounds": sv["recovery_rounds"],
        "recovery_s": sv["t_rec"]})


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
