"""Sharded multi-rank GNN serving dry-run.

Stands up one serving shard per host device over a partitioned synthetic
graph and reports routing/throughput/halo-gather behavior:

  python -m repro.launch.gnn_serve_dist [--ranks 4] [--vertices 20000]
                                        [--slots 32] [--queries 1024]
                                        [--policy degree] [--prewarm-frac .25]

Flow: synthetic power-law graph -> min-cut partitions -> per-shard caches
pre-warmed by **distributed offline inference** under the selected policy
(default: degree-weighted — hubs dominate sampled neighborhoods, so they
buy the most leaf-rate per cache line) -> ``DistGNNServeScheduler`` routes
a query workload to owner shards and serves it with per-layer halo
all_to_all gathers.  Complements ``gnn_serve`` (single-rank) with the
scale-out story.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--vertices", type=int, default=20_000)
    ap.add_argument("--model", default="graphsage",
                    choices=["graphsage", "gat"])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--halo-slots", type=int, default=256)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--overlap", type=float, default=0.5,
                    help="fraction of queries that repeat earlier ones")
    ap.add_argument("--cache-size", type=int, default=65_536)
    ap.add_argument("--policy", default="degree",
                    choices=["degree", "query_log", "none"],
                    help="cache pre-warm policy (default degree-weighted)")
    ap.add_argument("--prewarm-frac", type=float, default=None,
                    help="override the policy's default fraction "
                         "(degree: 0.25, query_log: 1.0)")
    ap.add_argument("--hot-size", type=int, default=2048,
                    help="replicated hot-vertex tier slots (0 disables)")
    ap.add_argument("--no-dedup", action="store_true",
                    help="disable cross-query neighborhood dedup")
    ap.add_argument("--round-batch", type=int, default=4,
                    help="serve rounds fused into one step/collective")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the serve "
                         "rounds (serve_round / serve_sample spans)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the obs registry as JSONL")
    ap.add_argument("--flight-dir", default=".", metavar="DIR",
                    help="where the health plane dumps FLIGHT_*.json on a "
                         "detection or an escaped exception")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="arm the SLO-burn detector with this p99 target")
    ap.add_argument("--audit-interval", type=int, default=0, metavar="N",
                    help="N > 0: run the exactness audit after each serve "
                         "pass (sampled cached embeddings vs distributed "
                         "offline recompute, relative-L2 error)")
    ap.add_argument("--quality-budget", type=float, default=None,
                    metavar="ERR",
                    help="arm the quality-budget detector: audit mean "
                         "error persistently above ERR dumps "
                         "FLIGHT_quality.json")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="periodically write the registry in Prometheus "
                         "text format (node-exporter textfile collector)")
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.ranks}")
    import jax
    from repro import obs
    from repro.configs.gnn import small_gnn_config
    from repro.graph import partition_graph, synthetic_graph
    from repro.launch.mesh import make_gnn_mesh
    from repro.serve.gnn import ServeCacheConfig, prewarm
    from repro.serve.gnn.distributed import (DistGNNServeScheduler,
                                             DistServeConfig)
    from repro.train.gnn_trainer import init_model_params

    obs.configure(obs.ObsConfig(
        trace=args.trace_out is not None, trace_path=args.trace_out,
        metrics_path=args.metrics_out))

    R = args.ranks
    g = synthetic_graph(num_vertices=args.vertices, avg_degree=8,
                        num_classes=16, feat_dim=32, seed=0)
    ps = partition_graph(g, R, seed=0)
    print(f"serving graph: {g.num_vertices} vertices over {R} shards, "
          f"edge cut {ps.edge_cut_frac:.2%}, shard sizes "
          f"{[p.num_solid for p in ps.parts]}")

    cfg = small_gnn_config(args.model, batch_size=64, feat_dim=32,
                           num_classes=16, fanouts=(5, 10), hidden_size=64)
    params = init_model_params(jax.random.key(0), cfg)
    # health plane: skew/drift over the serve-side halo series (expected
    # distribution = the partitioning's per-rank halo counts), optional
    # SLO burn, flight recorder on anomalies
    health = obs.HealthPlane(
        obs.HealthConfig(
            flight_dir=args.flight_dir,
            skew_metric="rank_serve_halo_rows",
            hot_metric="rank_serve_hot_hits",
            slo_p99_s=args.slo_p99_ms / 1e3
            if args.slo_p99_ms is not None else None,
            quality_budget=args.quality_budget),
        num_ranks=R,
        expected_halo_rows=[p.num_halo for p in ps.parts])
    prom = obs.PromFileWriter(args.prom_out, min_interval_s=1.0) \
        if args.prom_out else None
    quality = obs.QualityPlane(
        obs.QualityConfig(audit_interval=args.audit_interval),
        health=health, prom=prom) if args.audit_interval else None
    srv = DistGNNServeScheduler(
        cfg, params, ps, make_gnn_mesh(R),
        DistServeConfig(num_slots=args.slots, halo_slots=args.halo_slots,
                        cache=ServeCacheConfig(cache_size=args.cache_size,
                                               ways=8),
                        hot_size=args.hot_size, dedup=not args.no_dedup,
                        round_batch=args.round_batch),
        health=health, quality=quality)

    def maybe_audit(label):
        if quality is None:
            return
        rep = srv.audit()
        fmt = "n/a" if rep.mean_err is None else f"{rep.mean_err:.5f}"
        hot_n = rep.hot["n"] if rep.hot else 0
        print(f"audit:      [{label}] mean rel-L2 err={fmt} over "
              f"{sum(v['n'] for v in rep.per_layer.values())} cache lines "
              f"+ {hot_n} hot replicas")
        if prom is not None:
            prom.maybe_write(obs.get().registry)
    if srv.hot is not None:
        print(f"hot tier:   {srv.hot.num_slots} hub vertices replicated on "
              f"every shard; dedup={not args.no_dedup}, "
              f"round_batch={args.round_batch}")

    rng = np.random.default_rng(0)
    n_unique = max(1, int(round(args.queries * (1 - args.overlap))))
    pool = rng.choice(g.num_vertices, size=n_unique, replace=False)
    vids = np.concatenate(
        [pool, rng.choice(pool, size=args.queries - n_unique, replace=True)])
    rng.shuffle(vids)

    # compile outside any reported timing, then reset cache AND counters
    srv.serve(vids[:2 * args.slots * R])
    srv.update_params(params)
    srv.cache.reset_counters()
    srv.reset_frontend()

    if args.policy != "none":
        t0 = time.perf_counter()
        n = prewarm(srv, policy=args.policy, frac=args.prewarm_frac,
                    query_log=vids if args.policy == "query_log" else None)
        print(f"pre-warm:   policy={args.policy} stored {n} vertices/layer "
              f"across {R} shards in {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    with health.guard("serve_rounds"):
        srv.serve(vids)
    dt = time.perf_counter() - t0
    m = srv.metrics()
    print(f"serve:      {args.queries} queries in {dt:.3f}s "
          f"({args.queries / dt:.0f} q/s), {m['steps_run']} rounds, "
          f"{m['fast_path_hits']} fast-path answers; "
          f"latency p50={m['latency_p50_ms']:.1f}ms "
          f"p99={m['latency_p99_ms']:.1f}ms")
    print(f"halo:       {m['halo_seen']} rows seen, "
          f"{m['halo_local_hits']} served locally "
          f"(cached-halo frac {m['cached_halo_frac']:.2f}), "
          f"{m['halo_fetched']} fetched via all_to_all "
          f"({m['halo_requested']} remote-fetch rows traveled)")
    if srv.hot is not None:
        print(f"heavy tail: {m['hot_hits']} hub rows from the local "
              f"replica, {m['hot_fast_path_hits']} tier fast-path "
              f"answers, {m['dedup_merged']} queries deduped into "
              f"shared slots")
    maybe_audit("pass1")

    # repeat pass: overlapping neighborhoods now resident per shard
    srv.cache.reset_counters()
    srv.reset_frontend()
    t0 = time.perf_counter()
    with health.guard("serve_rounds"):
        srv.serve(vids)
    dt2 = time.perf_counter() - t0
    m = srv.metrics()
    print(f"repeat:     {args.queries} queries in {dt2:.3f}s "
          f"({args.queries / dt2:.0f} q/s), {m['fast_path_hits']} fast-path, "
          f"cached-halo frac {m['cached_halo_frac']:.2f} -> "
          f"{dt / max(dt2, 1e-9):.1f}x first pass")
    maybe_audit("repeat")

    hs = health.summary()
    fmt = lambda v, spec=".3f": "n/a" if v is None else f"{v:{spec}}"
    print(f"health:     {hs['windows']} rounds observed, halo skew="
          f"{fmt(hs['skew'], '.2f')}, edge-cut drift="
          f"{fmt(hs['edge_cut_drift'])}, slo burn={fmt(hs['slo_burn'])}, "
          f"{len(hs['detections'])} detections")
    for p in hs["flight_paths"]:
        print(f"flight:     {p}")

    if prom is not None:
        print(f"wrote {prom.write(obs.get().registry)}")
    for path in obs.flush():
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
