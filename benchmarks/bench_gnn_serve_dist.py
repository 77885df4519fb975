"""Sharded GNN serving benchmark (serve/gnn/distributed subsystem).

Measures shard-count scaling on a **cut-heavy** synthetic graph (low
intra-community edge probability, so sampled neighborhoods cross the
partition cut constantly — the adversarial case for sharded serving),
with a **repeat-heavy power-law query stream** (hub vertices are queried
disproportionately often, like production traffic):

  * **single-rank baseline**: the PR 2 ``GNNServeScheduler`` over the
    whole graph,
  * **R=4 baseline (PR 4)**: ``DistGNNServeScheduler`` with the PR 5
    features OFF — per-layer halo all_to_all + sharded cache,
  * **R=4 optimized (PR 5)**: hot-vertex tier + cross-query dedup +
    multi-round fused exchange batching, same query volume,
  * **remote-fetch rows/bytes**: the rows that actually traveled through
    ``cache_fetch`` (plus the tier's one-off warm broadcast, amortized
    into the optimized total) — baseline vs optimized is the heavy-tail
    win, directly visible in the smoke output and gated in CI,
  * **steady-state throughput**: queries answered per round / modeled
    round latency (round = measured / R as in bench_scaling, since this
    container serializes shard steps that run concurrently on a cluster).

Acceptance (non-smoke): optimized remote-fetch rows reduced >= 50% vs the
PR 4 baseline, and optimized steady-state throughput >= 1.3x the PR 4
baseline.  The remote-rows reduction (strict) is a CI gate even at smoke
scale, so the optimization can't silently regress to a no-op.

Emits ``name,us_per_call,derived`` CSV rows plus one ``RESULT{...}`` JSON
line.  Runs in subprocesses so each rank count gets its own XLA device
count.

Its child processes are pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
see ``benchmarks.common.cpu_child_env``).
"""
from __future__ import annotations

import json
import subprocess
import sys

from benchmarks.common import cpu_child_env, emit, result

_SCRIPT = r"""
import os, sys, json, time
R = int(sys.argv[1]); V = int(sys.argv[2]); Q = int(sys.argv[3])
OPT = sys.argv[4] == "opt"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import jax, numpy as np
from repro.cache import ServeCacheConfig       # the unified cache (PR 4)
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.serve.gnn import (GNNServeConfig, GNNServeScheduler,
                             select_prewarm_vids)
from repro.serve.gnn.distributed import (DistGNNServeScheduler,
                                         DistServeConfig,
                                         layerwise_embeddings_dist)
from repro.train.gnn_trainer import init_model_params

SLOTS = 32
NB = 4 if OPT else 1                 # rounds fused per compiled step
# intra_prob 0.35 => most edges cross communities => heavy partition cut;
# production-ish model size so forward compute (not per-round dispatch)
# dominates the measurement
g = synthetic_graph(num_vertices=V, avg_degree=12, num_classes=16,
                    feat_dim=64, seed=0, intra_prob=0.35)
ps = partition_graph(g, R, seed=0)
cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=64,
                       num_classes=16, fanouts=(10, 15), hidden_size=128)
params = init_model_params(jax.random.key(0), cfg)
cache = ServeCacheConfig(cache_size=65536, ways=8)
HOT = V // 2 if OPT else 0           # the hub slice: top-degree halo'd vids
if R == 1:
    srv = GNNServeScheduler(cfg, params, ps.parts[0],
                            GNNServeConfig(num_slots=SLOTS, cache=cache))
else:
    srv = DistGNNServeScheduler(
        cfg, params, ps, make_gnn_mesh(R),
        DistServeConfig(num_slots=SLOTS, halo_slots=256, cache=cache,
                        hot_size=HOT, dedup=OPT, round_batch=NB))

# power-law query stream: hub-popularity-weighted WITH repeats — the
# production shape the dedup + hot-tier path is built for
from repro.comm.plan import partition_degrees
rng = np.random.default_rng(0)
deg = partition_degrees(ps).astype(np.float64)
pop = deg / deg.sum()
sets = [rng.choice(V, size=Q, replace=True, p=pop) for _ in range(4)]

srv.serve(rng.integers(0, V, 2 * SLOTS * R * NB))  # compile outside timings
srv.update_params(params)                      # clear cache, keep compiled

# production regime: hidden layers pre-warmed from distributed offline
# inference (answers stay on the compute path but halo gathers are
# answerable); the optimized config additionally broadcasts the hot set
# into every shard's tier replica — counted against its remote rows
warm_rows = 0
if R > 1:
    embs = layerwise_embeddings_dist(cfg, params, ps, chunk_size=2048)
    warm_vids = select_prewarm_vids(ps.parts, "degree", frac=0.6)
    srv.cache.warm(embs, warm_vids, layers=range(cfg.num_layers - 1))
    if OPT and srv.hot is not None:
        srv.hot.warm(embs)
        warm_rows = srv.hot.num_slots * (R - 1)

passes = []
for s in sets[:3]:
    srv.cache.reset_counters()
    srv.reset_frontend()
    if getattr(srv, "hot", None) is not None:
        srv.hot.reset_counters()
    t0 = time.perf_counter()
    srv.serve(s)
    dt = time.perf_counter() - t0
    m = srv.metrics()
    passes.append({
        "qps": Q / dt, "steps": m["steps_run"],
        "dedup_merged": m.get("dedup_merged", 0),
        "fast_path": m.get("fast_path_hits", 0)
        + m.get("hot_fast_path_hits", 0),
        "hot_hits": m.get("hot_hits", 0),
        "halo_seen": m.get("halo_seen", 0),
        "halo_local": m.get("halo_local_hits", 0),
        "halo_fetched": m.get("halo_fetched", 0),
        "halo_requested": m.get("halo_requested", 0),
        "cached_halo_frac": m.get("cached_halo_frac", 0.0)})

# steady-state round probe: one FULL compiled step (per shard), fixed,
# timed over reps — the per-round cost the cluster model scales by 1/R
import jax.numpy as jnp
if R == 1:
    mb = srv._sample(rng.integers(0, V, SLOTS))
    call = lambda: srv._step(srv.params, srv.cache.states, srv.features, mb)
else:
    from repro.pipeline.vectorized_sampler import (concat_blocks,
                                                   sample_blocks_vectorized,
                                                   stack_ranks)
    blocks = []
    for q in range(R):
        segs = [sample_blocks_vectorized(
            ps.parts[q], rng.integers(0, ps.parts[q].num_solid, SLOTS),
            cfg.fanouts, np.random.default_rng([1, q, n]), SLOTS,
            expandable=srv._expandable(q)) for n in range(NB)]
        blocks.append(concat_blocks(segs))
    mb = jax.tree_util.tree_map(jnp.asarray, stack_ranks(blocks))
    tstates = srv.hot.states if srv.hot is not None else []
    call = lambda: srv._step(srv.params, srv.cache.states, tstates,
                             srv.data, mb)
jax.block_until_ready(call()[0])
reps = 3 if Q <= 128 else 8
t0 = time.perf_counter()
for _ in range(reps):
    jax.block_until_ready(call()[0])
t_round = (time.perf_counter() - t0) / reps
print("RESULT" + json.dumps({
    "ranks": R, "opt": OPT, "edge_cut_frac": ps.edge_cut_frac,
    "passes": passes, "t_round": t_round, "slots": SLOTS,
    "round_batch": NB, "hot_size": HOT, "warm_rows": warm_rows,
    "queries": Q, "hidden": cfg.hidden_size}))
"""


def _run(R, V, Q, mode="base"):
    env = cpu_child_env()
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(R), str(V), str(Q), mode],
        capture_output=True, text=True, env=env, check=False)
    if out.returncode != 0:
        raise RuntimeError(f"rank={R} ({mode}) child failed:\n"
                           f"{out.stderr[-4000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def _steady_qps(run):
    """Queries answered per round / modeled round latency (round latency =
    measured / R: the shard steps this container serializes run
    concurrently on the cluster, as in bench_scaling)."""
    rounds = max(sum(p["steps"] for p in run["passes"]), 1)
    q_per_round = 3 * run["queries"] / rounds
    return q_per_round / (run["t_round"] / run["ranks"])


def main(smoke=False):
    # Q deep enough that per-shard queues hold several rounds' worth of
    # work — the regime multi-round batching (and a production server
    # under load) actually runs in
    V = 1500 if smoke else 12_000
    Q = 512 if smoke else 2048
    single = _run(1, V, Q)
    base = _run(4, V, Q, "base")
    opt = _run(4, V, Q, "opt")
    R = base["ranks"]
    slots = base["slots"]
    qps_probe_1 = slots / single["t_round"]
    qps_base = _steady_qps(base)
    qps_opt = _steady_qps(opt)
    speedup_vs_single = qps_base / qps_probe_1
    speedup_opt = qps_opt / qps_base

    # remote-fetch rows: what actually traveled through cache_fetch over
    # the three passes, plus the tier's warm broadcast AMORTIZED over the
    # checkpoint lifetime (replicas stay valid until the next
    # update_params; a production server refreshes once per checkpoint,
    # so the broadcast is paid once per CKPT_ROUNDS serve rounds and this
    # window covers only `rounds_run` of them) — the modeled piece of the
    # otherwise-measured comparison
    CKPT_ROUNDS = 256
    dim = base["hidden"]                         # hidden width (payload f32)
    rounds_run = max(sum(p["steps"] for p in opt["passes"]), 1)
    charged_warm = opt["warm_rows"] * min(rounds_run / CKPT_ROUNDS, 1.0)
    rows_base = sum(p["halo_requested"] for p in base["passes"])
    rows_opt = sum(p["halo_requested"] for p in opt["passes"]) \
        + int(round(charged_warm))
    bytes_base = rows_base * (4 + 4 * dim)
    bytes_opt = rows_opt * (4 + 4 * dim)
    reduction = 1.0 - rows_opt / max(rows_base, 1)

    emit("gnn_serve_dist_single", single["t_round"] * 1e6,
         f"step_qps={qps_probe_1:.0f};"
         f"pump_qps_p1={single['passes'][0]['qps']:.0f}")
    emit("gnn_serve_dist_r4", base["t_round"] * 1e6,
         f"steady_qps={qps_base:.0f};"
         f"vs_single={speedup_vs_single:.1f}x;"
         f"edge_cut={base['edge_cut_frac']:.2f};"
         f"remote_rows={rows_base};remote_bytes={bytes_base}")
    emit("gnn_serve_dist_r4_opt", opt["t_round"] * 1e6,
         f"steady_qps={qps_opt:.0f};vs_base={speedup_opt:.2f}x;"
         f"round_batch={opt['round_batch']};hot_size={opt['hot_size']};"
         f"remote_rows={rows_opt};remote_bytes={bytes_opt};"
         f"reduction={reduction:.2f};"
         f"dedup_merged={sum(p['dedup_merged'] for p in opt['passes'])};"
         f"hot_hits={sum(p['hot_hits'] for p in opt['passes'])};"
         f"fast_path={sum(p['fast_path'] for p in opt['passes'])}")
    fracs = [p["cached_halo_frac"] for p in base["passes"]]
    emit("gnn_serve_dist_halo", 1e6 / base["passes"][-1]["qps"],
         f"cached_halo_frac_by_pass="
         + "/".join(f"{f:.3f}" for f in fracs)
         + f";halo_fetched_p1={base['passes'][0]['halo_fetched']}")
    assert base["passes"][0]["halo_seen"] > 0, \
        "cut-heavy graph produced no halo traffic"
    # PERF GATE (runs in --smoke too): the hot tier + dedup + batching must
    # cut remote-fetch rows vs the PR 4 baseline on the power-law stream
    assert rows_opt < rows_base, \
        f"optimized serving must reduce remote-fetch rows: " \
        f"{rows_opt} vs {rows_base}"
    if not smoke:       # wall-clock bars don't gate the tiny-scale CI pass
        assert reduction >= 0.5, \
            f"remote-fetch rows must drop >= 50% vs the PR 4 baseline, " \
            f"got {reduction:.2f}"
        assert speedup_opt >= 1.3, \
            f"optimized steady-state throughput must be >= 1.3x the PR 4 " \
            f"baseline, got {speedup_opt:.2f}x"
        assert speedup_vs_single >= 2.0, \
            f"modeled R=4 steady-state serving must be >= 2x single-rank, " \
            f"got {speedup_vs_single:.2f}x"
    result({
        "steady_qps_single_probe": qps_probe_1,
        "steady_qps_base": qps_base,
        "steady_qps_opt": qps_opt,
        "speedup_vs_single": speedup_vs_single,
        "speedup_opt_vs_base": speedup_opt,
        "remote_rows_base": rows_base, "remote_rows_opt": rows_opt,
        "remote_bytes_base": bytes_base, "remote_bytes_opt": bytes_opt,
        "remote_rows_reduction": reduction,
        "round_us_base": base["t_round"] * 1e6,
        "round_us_opt": opt["t_round"] * 1e6,
        "edge_cut_frac": base["edge_cut_frac"],
        "dedup_merged": sum(p["dedup_merged"] for p in opt["passes"]),
        "hot_hits": sum(p["hot_hits"] for p in opt["passes"]),
        "cached_halo_frac_by_pass": fracs})


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
