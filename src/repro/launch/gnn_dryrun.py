import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=64 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Paper-scale GNN dry-run: lower + compile the DistGNN-MB training step at
64 ranks (the paper's largest configuration) and report the roofline terms
+ the AEP collective schedule.

  python -m repro.launch.gnn_dryrun [--ranks 64] [--model graphsage]

This complements the LM-architecture dry-run (repro.launch.dryrun): it
proves the shard_map program — HEC tick/store/search, db_halo membership,
degree-reservoir push selection, delay-d in-flight queue, all_to_all, pmean
gradient all-reduce — partitions cleanly at paper scale.
"""
import argparse
import time

import numpy as np


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=64)
    ap.add_argument("--model", default="graphsage",
                    choices=["graphsage", "gat"])
    ap.add_argument("--vertices", type=int, default=30_000)
    ap.add_argument("--mode", default="aep", choices=["aep", "sync", "drop"])
    ap.add_argument("--hot-size", type=int, default=0,
                    help="replicated hot-vertex tier slots (0 disables); "
                         "refreshes ride the fused AEP push")
    ap.add_argument("--hot-budget", type=int, default=256,
                    help="hot rows broadcast per rank per step")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the dry-run's "
                         "phase spans (load in chrome://tracing / Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the obs registry as JSONL")
    ap.add_argument("--flight-dir", default=".", metavar="DIR",
                    help="where the health plane dumps FLIGHT_*.json on a "
                         "detection or an escaped exception")
    args = ap.parse_args()

    import jax
    from repro import obs
    from repro.configs.gnn import HECConfig, small_gnn_config
    from repro.graph import partition_graph, synthetic_graph
    from repro.launch.mesh import ICI_BW, HBM_BW, PEAK_FLOPS_BF16, make_gnn_mesh
    from repro.pipeline import MinibatchPipeline
    from repro.train.gnn_trainer import DistTrainer, build_dist_data
    from repro.utils import hlo_cost

    obs.configure(obs.ObsConfig(
        trace=args.trace_out is not None, trace_path=args.trace_out,
        metrics_path=args.metrics_out))

    R = args.ranks
    g = synthetic_graph(num_vertices=args.vertices, avg_degree=10,
                        num_classes=16, feat_dim=128, seed=0)
    t0 = time.time()
    ps = partition_graph(g, R, seed=0)
    print(f"partitioned V={g.num_vertices} into {R} ranks in "
          f"{time.time()-t0:.1f}s; edge-cut={ps.edge_cut_frac:.3f}; "
          f"train/rank={[int(p.train_mask.sum()) for p in ps.parts[:4]]}...")

    cfg = small_gnn_config(
        args.model, batch_size=256, feat_dim=128, num_classes=16,
        fanouts=(5, 10), hidden_size=256,
        hec=HECConfig(cache_size=65_536, ways=8, life_span=2,
                      push_limit=1024, delay=1, hot_size=args.hot_size,
                      hot_budget=args.hot_budget if args.hot_size else 0))
    dd = build_dist_data(ps, cfg)
    mesh = make_gnn_mesh(R)
    tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=R, mode=args.mode)
    state = tr.init_state(jax.random.key(0), dd)
    if state["hot"]:
        K = dd["hot_vids"].shape[1]
        print(f"hot tier: {K} hub vertices replicated per rank; refresh "
              f"budget {args.hot_budget}/rank/step rides the fused push "
              f"(hot vids left the pairwise push contract)")

    # minibatch via the async pipeline's sampling plan (vectorized CSR
    # sampler; sampled inline so the timing is exactly one batch and no
    # prefetch worker outlives this measurement)
    pipe = MinibatchPipeline(ps, cfg, base_seed=0)
    sched = pipe.plan.epoch_schedule(0)
    t0 = time.time()
    mb = jax.block_until_ready(
        jax.device_put(pipe.plan.sample_host(0, 0, sched[0])))
    print(f"pipeline minibatch (vectorized sampler): one {R}-rank batch "
          f"sampled+staged in {time.time()-t0:.2f}s; training runs it with "
          f"{cfg.pipeline.num_workers} prefetch workers, depth "
          f"{cfg.pipeline.prefetch_depth}")

    step = tr.make_step(donate=False)
    t0 = time.time()
    lowered = step.lower(state["params"], state["opt_state"], state["hec"],
                         state["hot"], state["inflight"], dd, mb,
                         np.uint32(0))
    compiled = lowered.compile()
    print(f"lower+compile at {R} ranks: {time.time()-t0:.1f}s")
    mem = compiled.memory_analysis()
    print(f"memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
          f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB per device")
    r = hlo_cost.analyze(compiled.as_text())
    print(f"per-device per-step: flops={r['flops']:.3e} "
          f"bytes={r['bytes_accessed']:.3e} "
          f"collective_bytes={r['collective_bytes']:.3e}")
    print("collective schedule:")
    for k, v in sorted(r["collectives"].items()):
        print(f"  {k:20s} count={v['count']:.0f} bytes={v['bytes']:.3e}")
    terms = {
        "compute_s": r["flops"] / PEAK_FLOPS_BF16,
        "memory_s": r["bytes_accessed"] / HBM_BW,
        "collective_s": r["collective_bytes"] / ICI_BW,
    }
    dom = max(terms, key=terms.get)
    print(f"roofline: compute={terms['compute_s']*1e3:.3f}ms "
          f"memory={terms['memory_s']*1e3:.3f}ms "
          f"collective={terms['collective_s']*1e3:.3f}ms -> {dom} bound")
    a2a = r["collectives"].get("all-to-all", {"count": 0, "bytes": 0.0})
    # one StepModel drives BOTH the overlap print and the epoch breakdown,
    # so the two figures can never disagree
    model = obs.StepModel.from_roofline(
        r["flops"], r["bytes_accessed"],
        a2a["bytes"] if args.mode == "aep" else 0.0,
        PEAK_FLOPS_BF16, HBM_BW, ICI_BW)
    if args.mode == "aep":
        assert a2a["count"] >= 1, \
            "AEP must lower to the engine's fused all-to-all push"
        hidden = model.overlap_efficiency()
        print(f"AEP fused all_to_all: {a2a['count']:.0f} op(s) "
              f"({a2a['bytes']:.3e} B/device/step) — the engine's push, "
              f"dispatched between forward and backward (overlap mode)")
        print(f"overlap: {a2a['bytes']:.3e} B/step overlapped behind the "
              f"backward pass; modeled push latency hidden "
              f"{hidden*100:.0f}% (push {model.push_s*1e6:.3f}us vs modeled "
              f"backward {model.bwd_s*1e6:.3f}us of "
              f"{model.work_s*1e6:.3f}us step work)")

    # execute the compiled step once: the measured wall time is split
    # fwd / exposed-push / bwd by the roofline model (the step is ONE
    # fused XLA program — its interior cannot be host-timed), and the
    # modeled sub-phases are emitted as trace spans on virtual tracks
    health = obs.HealthPlane(
        obs.HealthConfig(flight_dir=args.flight_dir), num_ranks=R,
        expected_halo_rows=[p.num_halo for p in ps.parts])
    with health.guard("dryrun_step"), obs.span("step", step=0):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(
            state["params"], state["opt_state"], state["hec"], state["hot"],
            state["inflight"], dd, mb, np.uint32(0)))
        t_step = time.perf_counter() - t0
    # per-rank telemetry shard of the executed step -> one health window
    import jax.tree_util as jtu
    acc = health.new_accumulator()
    acc.add(jtu.tree_map(np.asarray, out[5]))
    totals = acc.finish()
    totals["rank_step_seconds"] = np.full(R, t_step)
    obs.publish_rank_series(obs.get().registry, totals)
    health.observe_epoch(totals, wall_s=t_step)
    halo = totals["rank_halo_rows"]
    skew = obs.skew_ratio(halo)
    print(f"health: per-rank halo rows min={halo.min():.0f} "
          f"max={halo.max():.0f} "
          f"skew={'n/a' if skew is None else f'{skew:.2f}'}; "
          f"{len(health.detections)} detections")
    fwd_s, push_s, bwd_s = model.split_step(t_step)
    tracer = obs.get().tracer
    if tracer.enabled:
        scale = t_step / model.step_s if model.step_s > 0 else 0.0
        base = t0 - tracer.epoch
        tracer.add_complete("fwd", base, fwd_s, track="device (modeled)")
        tracer.add_complete("bwd", base + fwd_s, bwd_s + push_s,
                            track="device (modeled)")
        # the push is dispatched after forward and hidden behind backward;
        # only its `push_s` tail (the exposed part) extends past bwd
        tracer.add_complete("aep_push", base + fwd_s,
                            model.push_s * scale, track="comm (modeled)")

    reg = obs.get().registry
    bd = obs.EpochBreakdown(model)
    bd.add_epoch(sample=reg.value("phase_seconds", phase="sample"),
                 host_prep=reg.value("phase_seconds", phase="host_prep"),
                 stage=reg.value("phase_seconds", phase="stage"),
                 step=t_step)
    print("epoch breakdown (1 step; device step split by the roofline "
          "model):")
    print(bd.table())
    for path in obs.flush():
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
