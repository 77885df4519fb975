"""Halo-exchange engine benchmark (repro.comm subsystem, PR 4 + PR 5).

Measures the wins of the unified exchange path at R=4:

  * **exchange-plan build** — the one-time host cost that replaces every
    per-step index computation (db membership, sorted owner tables,
    offline gather/scatter indices),
  * **plan gather vs legacy per-step probes** — AEP push-contract
    membership as ONE ``push_mask`` boolean gather vs the pre-refactor
    per-rank-pair ``searchsorted`` probes (both jitted, same inputs),
  * **fused vs split push collective** — tags bitcast into the payload of
    ONE ``all_to_all`` vs the legacy two collectives (shard_map probe at
    trainer payload shapes),
  * **compute-communication overlap** — full training steps with the push
    dispatched between forward and backward (``overlap=True``) vs inline
    after the backward, plus the isolated push-collective latency,
  * **hot-vertex tier (PR 5)** — remote-fetch rows with the replicated
    hub tier on vs off: the plan's degree-weighted appearance model
    (``ExchangePlan.modeled_remote_rows``) over a refresh window, plus
    measured training steps (pairwise push rows shrink, the broadcast
    refresh rides the same collective, tier hits replace HEC hits).
    The modeled comparison is a CI gate even at smoke scale: the tier
    must cut modeled remote rows or the optimization has regressed to a
    no-op.

This container time-shares all host devices on a couple of cores and XLA
CPU serializes collectives with compute, so measured overlap wall-clock is
reported but the acceptance number is **modeled** the way the paper's §4.4
epoch-time structure does (and bench_scaling/bench_distdgl already do):
an overlapped step costs max(compute, push) instead of compute + push, so
the push latency hidden is min(push, compute) / push — 100% whenever the
push is smaller than the backward it hides under.

Emits ``name,us_per_call,derived`` CSV rows plus one ``RESULT{...}`` JSON
line.  Runs in a subprocess so the rank count gets its own XLA device
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
R = int(sys.argv[1]); V = int(sys.argv[2]); REPS = int(sys.argv[3])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R}"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm.engine import HaloExchangeEngine
from repro.comm.plan import build_exchange_plan, partition_degrees
from repro.configs.gnn import HECConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.pipeline import MinibatchPipeline
from repro.train.gnn_trainer import DistTrainer, build_dist_data, layer_dims

def timeit(fn, reps):
    fn()                                   # compile / warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps

g = synthetic_graph(num_vertices=V, avg_degree=8, num_classes=8,
                    feat_dim=32, seed=0, intra_prob=0.35)  # cut-heavy
ps = partition_graph(g, R, seed=0)
t0 = time.perf_counter()
plan = build_exchange_plan(ps)
t_plan = time.perf_counter() - t0

cfg = small_gnn_config("graphsage", batch_size=64, feat_dim=32,
                       num_classes=8,
                       hec=HECConfig(cache_size=8192, ways=4, life_span=2,
                                     push_limit=256, delay=1))
dims = layer_dims(cfg)
dmax = max(dims)
L = cfg.num_layers
nc = cfg.hec.push_limit
mesh = make_gnn_mesh(R)
dd = build_dist_data(ps, cfg)

# -- (1) push-contract membership: legacy per-step probes vs plan gather ----
rng = np.random.default_rng(0)
N0 = 4 * cfg.batch_size
nodes = jnp.asarray(rng.integers(0, ps.parts[0].num_solid, N0), jnp.int32)
vid0 = jnp.asarray(np.asarray(ps.parts[0].vid_p_to_o())[np.asarray(nodes)],
                   jnp.int32)
db0 = jnp.asarray(plan.db_halo[0])       # [R, D] rank-0 slice
pm0 = jnp.asarray(plan.push_mask[0])     # [R, Pmax] rank-0 slice

@jax.jit
def legacy_membership(vid0):
    outs = []
    for j in range(R):
        dbj = db0[j]
        loc = jnp.clip(jnp.searchsorted(dbj, vid0), 0, dbj.shape[0] - 1)
        outs.append(dbj[loc] == vid0)
    return jnp.stack(outs)

@jax.jit
def plan_membership(nodes):
    return pm0[:, jnp.clip(nodes, 0, pm0.shape[1] - 1)]

m_legacy = np.asarray(legacy_membership(vid0))
m_plan = np.asarray(plan_membership(nodes))
assert (m_legacy == m_plan).all(), "plan gather must equal legacy probes"
t_legacy_mem = timeit(lambda: jax.block_until_ready(legacy_membership(vid0)),
                      REPS * 4)
t_plan_mem = timeit(lambda: jax.block_until_ready(plan_membership(nodes)),
                    REPS * 4)

# -- (2) push collective: ONE fused all_to_all vs legacy two ----------------
engine = HaloExchangeEngine(R, L, nc, axis="data")
tags = jnp.asarray(rng.integers(-1, V, (R, R, L, nc)), jnp.int32)
embs = jnp.asarray(rng.normal(size=(R, R, L, nc, dmax)), jnp.float32)

def fused(t, e):
    sq = lambda a: a[0]
    rt, re = engine.push(sq(t), sq(e))
    return rt[None], re[None]

def split(t, e):
    rt = jax.lax.all_to_all(t[0], "data", 0, 0)
    re = jax.lax.all_to_all(e[0], "data", 0, 0)
    return rt[None], re[None]

shard = P("data")
fused_sm = jax.jit(jax.shard_map(fused, mesh=mesh, in_specs=(shard, shard),
                                 out_specs=(shard, shard), check_vma=False))
split_sm = jax.jit(jax.shard_map(split, mesh=mesh, in_specs=(shard, shard),
                                 out_specs=(shard, shard), check_vma=False))
ft, fe = fused_sm(tags, embs)
st_, se = split_sm(tags, embs)
assert (np.asarray(ft) == np.asarray(st_)).all()
assert (np.asarray(fe) == np.asarray(se)).all()
t_fused = timeit(lambda: jax.block_until_ready(fused_sm(tags, embs)[1]), REPS)
t_split = timeit(lambda: jax.block_until_ready(split_sm(tags, embs)[1]), REPS)
push_bytes = R * L * nc * 4 * (1 + dmax)   # per-rank fused payload

# -- (3) overlap: dispatch-then-wait vs inline vs no-push -------------------
pipe = MinibatchPipeline(ps, cfg, base_seed=0)
sched = pipe.plan.epoch_schedule(0)
mb = jax.device_put(pipe.plan.sample_host(0, 0, sched[0]))

def step_time(mode, overlap):
    tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=R, mode=mode,
                     overlap=overlap)
    state = tr.init_state(jax.random.key(0))
    stepf = tr.make_step(donate=False)
    call = lambda: stepf(state["params"], state["opt_state"], state["hec"],
                         state["hot"], state["inflight"], dd, mb,
                         jnp.uint32(0))
    return timeit(lambda: jax.block_until_ready(call()[-1]["loss"]), REPS)

t_overlap = step_time("aep", True)
t_inline = step_time("aep", False)
t_drop = step_time("drop", False)
t_push = t_fused                       # measured isolated push latency
compute_s = max(t_overlap - t_push, t_drop)  # step compute the push hides under
hidden_modeled = min(t_push, compute_s) / t_push
hidden_measured = (t_inline - t_overlap) / t_push

# -- (4) hot-vertex tier: heavy-tail remote-fetch rows ----------------------
# modeled: degree-weighted appearance per replica over a refresh window
# (replicas refresh once per window, fetches recur every round); measured:
# one epoch with the tier on vs off — pairwise push rows shrink (hot vids
# leave the contract) while the broadcast refresh rides the SAME fused
# collective, and tier hits replace HEC hits for hub halos.
HOT = V // 2
deg = partition_degrees(ps)
plan_hot = build_exchange_plan(ps, hot_size=HOT)
W = 16                                  # rounds per refresh window
model = plan_hot.modeled_remote_rows(deg, rounds=W, refresh_every=W)

def epoch_stats(hot):
    hec = HECConfig(cache_size=8192, ways=4, life_span=2, push_limit=256,
                    delay=1, hot_size=HOT if hot else 0,
                    hot_budget=256 if hot else 0)
    c = small_gnn_config("graphsage", batch_size=64, feat_dim=32,
                         num_classes=8, hec=hec)
    ddh = build_dist_data(ps, c)
    tr = DistTrainer(cfg=c, mesh=mesh, num_ranks=R, mode="aep")
    st = tr.init_state(jax.random.key(0), ddh)
    st, hist = tr.train_epochs(ps, ddh, st, 2)
    m = hist[-1]
    return {"push_rows": m.get("aep_push_rows", 0.0),
            "hot_push_rows": m.get("hot_push_rows", 0.0),
            "hot_hits": sum(v for k, v in m.items()
                            if k.startswith("hot_hits_l")),
            "hit_rate_l0": m.get("hec_hits_l0", 0.0)
            / max(m.get("hec_halos_l0", 1.0), 1.0)}

tier_on = epoch_stats(True)
tier_off = epoch_stats(False)

print("RESULT" + json.dumps({
    "ranks": R, "edge_cut_frac": ps.edge_cut_frac,
    "t_plan_build": t_plan,
    "t_membership_legacy": t_legacy_mem, "t_membership_plan": t_plan_mem,
    "t_push_fused": t_fused, "t_push_split": t_split,
    "push_bytes_per_rank": push_bytes,
    "t_step_overlap": t_overlap, "t_step_inline": t_inline,
    "t_step_drop": t_drop, "t_push": t_push,
    "hidden_modeled": hidden_modeled, "hidden_measured": hidden_measured,
    "hot_size": plan_hot.hot_size,
    "remote_rows_model": model,
    "tier_on": tier_on, "tier_off": tier_off}))
"""


def _run(R, V, reps):
    env = cpu_child_env()
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(R), str(V), str(reps)],
        capture_output=True, text=True, env=env, check=False)
    if out.returncode != 0:
        raise RuntimeError(f"rank={R} child failed:\n{out.stderr[-4000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def main(smoke=False):
    V = 1500 if smoke else 8000
    reps = 3 if smoke else 10
    r = _run(4, V, reps)
    emit("comm_plan_build", r["t_plan_build"] * 1e6,
         f"edge_cut={r['edge_cut_frac']:.2f}")
    emit("comm_membership", r["t_membership_plan"] * 1e6,
         f"legacy_us={r['t_membership_legacy']*1e6:.1f};"
         f"speedup={r['t_membership_legacy']/r['t_membership_plan']:.1f}x")
    emit("comm_push_fused", r["t_push_fused"] * 1e6,
         f"split_us={r['t_push_split']*1e6:.1f};"
         f"bytes_per_rank={r['push_bytes_per_rank']}")
    emit("comm_overlap", r["t_step_overlap"] * 1e6,
         f"inline_us={r['t_step_inline']*1e6:.1f};"
         f"push_us={r['t_push']*1e6:.1f};"
         f"hidden_modeled={r['hidden_modeled']:.2f};"
         f"hidden_measured={r['hidden_measured']:.2f}")
    model = r["remote_rows_model"]
    on, off = r["tier_on"], r["tier_off"]
    emit("comm_hot_tier_remote_rows", model["hot_rows"],
         f"baseline_rows={model['baseline_rows']:.0f};"
         f"reduction={model['reduction']:.2f};"
         f"hot_size={r['hot_size']};window={model['rounds']}")
    emit("comm_hot_tier_push", on["push_rows"],
         f"push_rows_off={off['push_rows']:.0f};"
         f"hot_broadcast_rows={on['hot_push_rows']:.0f};"
         f"tier_hits_per_step={on['hot_hits']:.0f};"
         f"hit_rate_l0_on={on['hit_rate_l0']:.2f};"
         f"hit_rate_l0_off={off['hit_rate_l0']:.2f}")
    # PERF GATE (runs in --smoke too): the tier must cut modeled remote
    # rows vs tier-disabled on the synthetic power-law graph — otherwise
    # the heavy-tail optimization has silently regressed to a no-op
    assert model["hot_rows"] < model["baseline_rows"], \
        f"hot tier must reduce modeled remote rows: " \
        f"{model['hot_rows']:.0f} vs {model['baseline_rows']:.0f}"
    if not smoke:       # wall-clock bars don't gate the tiny-scale CI pass
        assert r["hidden_modeled"] >= 0.5, \
            f"overlap must hide >= 50% of the push latency (modeled), " \
            f"got {r['hidden_modeled']:.2f}"
        assert model["reduction"] >= 0.5, \
            f"hot tier must cut modeled remote-fetch rows >= 50% over a " \
            f"{model['rounds']}-round window, got {model['reduction']:.2f}"
    result(r)


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
