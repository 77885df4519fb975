"""GNN inference serving dry-run.

Exercises the full serving subsystem at a configurable scale and reports
throughput + cache behavior:

  python -m repro.launch.gnn_serve [--vertices 20000] [--model graphsage]
                                   [--slots 32] [--queries 1024]
                                   [--overlap 0.5] [--no-prewarm]

Flow: synthetic power-law graph -> single-partition serving graph ->
``GNNServeScheduler`` (fixed-slot microbatches, HEC-backed cache) serves a
query workload cold; the layer-wise offline engine then computes exact
full-graph embeddings, pre-warms the cache, and the same workload is served
again — the second pass answers from the output cache without sampling or
compute.  Complements ``gnn_dryrun`` (training-step compile at 64 ranks)
with the inference-side story.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=20_000)
    ap.add_argument("--model", default="graphsage",
                    choices=["graphsage", "gat"])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--overlap", type=float, default=0.5,
                    help="fraction of queries that repeat earlier ones")
    ap.add_argument("--cache-size", type=int, default=65_536)
    ap.add_argument("--no-prewarm", action="store_true")
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
                         "pass (sampled cached embeddings vs offline "
                         "recompute, relative-L2 error)")
    ap.add_argument("--quality-budget", type=float, default=None,
                    metavar="ERR",
                    help="arm the quality-budget detector: audit mean "
                         "error persistently above ERR dumps "
                         "FLIGHT_quality.json")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="periodically write the registry in Prometheus "
                         "text format (node-exporter textfile collector)")
    args = ap.parse_args()

    import jax
    from repro import obs
    from repro.configs.gnn import small_gnn_config
    from repro.graph import partition_graph, synthetic_graph
    from repro.serve.gnn import (GNNServeConfig, GNNServeScheduler,
                                 ServeCacheConfig, layerwise_embeddings,
                                 warm_cache)
    from repro.train.gnn_trainer import init_model_params

    obs.configure(obs.ObsConfig(
        trace=args.trace_out is not None, trace_path=args.trace_out,
        metrics_path=args.metrics_out))

    g = synthetic_graph(num_vertices=args.vertices, avg_degree=8,
                        num_classes=16, feat_dim=32, seed=0)
    part = partition_graph(g, 1, seed=0).parts[0]
    print(f"serving graph: {part.num_solid} vertices, "
          f"{len(part.indices)} edges")

    cfg = small_gnn_config(args.model, batch_size=64, feat_dim=32,
                           num_classes=16, fanouts=(5, 10), hidden_size=64)
    params = init_model_params(jax.random.key(0), cfg)
    health = obs.HealthPlane(
        obs.HealthConfig(
            flight_dir=args.flight_dir,
            slo_p99_s=args.slo_p99_ms / 1e3
            if args.slo_p99_ms is not None else None,
            quality_budget=args.quality_budget),
        num_ranks=1)
    prom = obs.PromFileWriter(args.prom_out, min_interval_s=1.0) \
        if args.prom_out else None
    quality = obs.QualityPlane(
        obs.QualityConfig(audit_interval=args.audit_interval),
        health=health, prom=prom) if args.audit_interval else None
    srv = GNNServeScheduler(
        cfg, params, part,
        GNNServeConfig(num_slots=args.slots,
                       cache=ServeCacheConfig(cache_size=args.cache_size,
                                              ways=8)),
        health=health, quality=quality)

    def maybe_audit(label):
        if quality is None:
            return
        rep = srv.audit()
        fmt = "n/a" if rep.mean_err is None else f"{rep.mean_err:.5f}"
        print(f"audit:      [{label}] mean rel-L2 err={fmt} over "
              f"{sum(v['n'] for v in rep.per_layer.values())} sampled lines")
        if prom is not None:
            prom.maybe_write(obs.get().registry)

    rng = np.random.default_rng(0)
    n_unique = max(1, int(round(args.queries * (1 - args.overlap))))
    pool = rng.choice(part.num_solid, size=n_unique, replace=False)
    vids = np.concatenate(
        [pool, rng.choice(pool, size=args.queries - n_unique, replace=True)])
    rng.shuffle(vids)

    # compile outside any reported timing, then reset cache AND counters so
    # the cold pass reports only its own lookups/hits
    srv.serve(vids[:2 * args.slots])
    srv.update_params(params)
    srv.cache.reset_counters()

    t0 = time.perf_counter()
    with health.guard("serve_rounds"):
        srv.serve(vids)
    t_cold = time.perf_counter() - t0
    m = srv.metrics()
    print(f"cold:       {args.queries} queries in {t_cold:.3f}s "
          f"({args.queries/t_cold:.0f} q/s), {m['steps_run']} microbatches; "
          f"hit rates "
          + " ".join(f"l{k}={m[f'hit_rate_l{k}']:.2f}"
                     for k in range(1, cfg.num_layers + 1))
          + f"; occupancy l1={m['occupancy_l1']:.2f}")
    maybe_audit("cold")

    if not args.no_prewarm:
        srv.update_params(params)
        t0 = time.perf_counter()
        embs = layerwise_embeddings(cfg, params, part)
        n = warm_cache(srv.cache, embs, np.unique(vids))
        t_warm_build = time.perf_counter() - t0
        print(f"pre-warm:   offline layer-wise inference + store of {n} "
              f"vertices in {t_warm_build:.3f}s")
        fp0 = srv.metrics()["fast_path_hits"]
        t0 = time.perf_counter()
        with health.guard("serve_rounds"):
            srv.serve(vids)
        t_warm = time.perf_counter() - t0
        m = srv.metrics()
        print(f"pre-warmed: {args.queries} queries in {t_warm:.3f}s "
              f"({args.queries/t_warm:.0f} q/s), "
              f"{m['fast_path_hits'] - fp0} fast-path answers -> "
              f"{t_cold/t_warm:.1f}x cold throughput")
        maybe_audit("warm")

    hs = health.summary()
    burn = hs["slo_burn"]
    print(f"health:     {hs['windows']} rounds observed, slo burn="
          f"{'n/a' if burn is None else f'{burn:.3f}'}, "
          f"{len(hs['detections'])} detections")
    for p in hs["flight_paths"]:
        print(f"flight:     {p}")

    if prom is not None:
        print(f"wrote {prom.write(obs.get().registry)}")
    for path in obs.flush():
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
