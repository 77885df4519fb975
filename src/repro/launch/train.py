"""Training launcher.

GNN (the paper's workload):
  python -m repro.launch.train gnn --model graphsage --ranks 4 \
      --vertices 20000 --epochs 5 --mode aep
  (one device per rank; on a CPU-only host, JAX_PLATFORMS=cpu
   XLA_FLAGS=--xla_force_host_platform_device_count=<ranks> provides them)

LM (assigned architectures, reduced configs on CPU):
  python -m repro.launch.train lm --arch minitron-4b --steps 20 \
      --batch 4 --seq 128
"""
from __future__ import annotations

import argparse
import time


def _configure_obs(args):
    """Shared --trace-out/--metrics-out plumbing: both the GNN and the LM
    subcommands feed the same registry sink (and write the same artifact
    formats) as the three GNN launchers."""
    from repro import obs
    obs.configure(obs.ObsConfig(
        trace=args.trace_out is not None, trace_path=args.trace_out,
        metrics_path=args.metrics_out))
    return obs


def _prom_writer(args, obs):
    """--prom-out plumbing: a periodic node-exporter-textfile-style
    export of the whole registry (quality/health gauges included)."""
    if getattr(args, "prom_out", None) is None:
        return None
    return obs.PromFileWriter(args.prom_out, min_interval_s=1.0)


def require_devices(n: int) -> None:
    """Exit unless JAX sees at least ``n`` devices (one per rank)."""
    import jax
    if jax.device_count() < n:
        raise SystemExit(f"need {n} devices, one per rank; JAX found "
                         f"{jax.device_count()}: {jax.devices()}")


def setup_gnn(ps, cfg, *, seed: int = 0, **trainer_kw):
    """Per-rank tables, mesh, trainer and initial state for a partitioned
    graph: the setup ``run_gnn`` and ``chip_smoke.py`` share.  Returns
    ``(dist_data, trainer, state)``; ``trainer_kw`` go to ``DistTrainer``."""
    import jax
    from repro.launch.mesh import make_gnn_mesh
    from repro.train.gnn_trainer import DistTrainer, build_dist_data

    R = ps.num_parts
    require_devices(R)
    mesh = make_gnn_mesh(R)
    dd = build_dist_data(ps, cfg, mesh)
    tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=R, **trainer_kw)
    state = tr.init_state(jax.random.key(seed), dd)
    return dd, tr, state


def run_gnn(args):
    from repro.configs.gnn import HECConfig, small_gnn_config
    from repro.graph import partition_graph, synthetic_graph
    from repro.train import checkpoint

    obs = _configure_obs(args)
    require_devices(args.ranks)

    g = synthetic_graph(num_vertices=args.vertices, avg_degree=args.degree,
                        num_classes=args.classes, feat_dim=args.feat_dim,
                        seed=args.seed)
    print(f"graph: V={g.num_vertices} E={g.num_edges} "
          f"train={int(g.train_mask.sum())}")
    ps = partition_graph(g, args.ranks, seed=args.seed)
    print(f"partitioned into {args.ranks}: edge-cut={ps.edge_cut_frac:.3f} "
          f"solids={[p.num_solid for p in ps.parts]}")
    cfg = small_gnn_config(
        args.model, batch_size=args.batch, feat_dim=args.feat_dim,
        num_classes=args.classes, fanouts=tuple(args.fanouts),
        hidden_size=args.hidden, num_hidden_layers=args.layers - 1,
        lr=args.lr,
        hec=HECConfig(cache_size=args.hec_size, ways=8,
                      life_span=args.hec_ls, push_limit=args.hec_nc,
                      delay=args.hec_delay))
    # cluster health plane: per-rank epoch series + skew/drift detectors
    # over the partitioning's expected halo distribution; train_epochs
    # dumps FLIGHT_*.json if a detector fires or the step loop dies
    health = obs.HealthPlane(
        obs.HealthConfig(flight_dir=args.flight_dir,
                         quality_budget=args.quality_budget),
        num_ranks=args.ranks,
        expected_halo_rows=[p.num_halo for p in ps.parts])
    # quality plane: staleness + convergence telemetry every epoch, the
    # exactness audit every --audit-interval epochs, budget breaches
    # routed through the health plane's FLIGHT_quality.json path
    prom = _prom_writer(args, obs)
    quality = obs.QualityPlane(
        obs.QualityConfig(audit_interval=args.audit_interval),
        health=health, prom=prom)
    # resilience plane: epoch-boundary checkpoints (+--resume), the
    # deterministic fault injector, and the NaN/Inf step guard.  With no
    # resilience flag set `rz` stays None and the trainer compiles the
    # exact unarmed step — byte-identical to a pre-resilience run.
    rz = None
    if (args.ckpt_dir or args.fault_schedule or args.nan_guard):
        from repro import resilience
        schedule = (resilience.FaultSchedule.from_json(args.fault_schedule)
                    if args.fault_schedule else None)
        rz = resilience.ResiliencePlane(resilience.ResilienceConfig(
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            ckpt_keep=args.ckpt_keep, nan_guard=args.nan_guard,
            schedule=schedule, flight_dir=args.flight_dir))
        if schedule is not None:
            print(f"fault schedule: {len(schedule.specs)} scheduled faults")
    dd, tr, state = setup_gnn(ps, cfg, seed=args.seed, mode=args.mode,
                              health=health, quality=quality, resilience=rz)
    start_epoch = 0
    if args.resume:
        if rz is None or rz.ckpt is None:
            raise SystemExit("--resume requires --ckpt-dir")
        state, saved_epoch = rz.ckpt.restore(state)
        start_epoch = saved_epoch + 1
        print(f"resumed from epoch {saved_epoch} "
              f"(step {int(state['step'])}); continuing at {start_epoch}")
    remaining = args.epochs - start_epoch
    if remaining <= 0:
        raise SystemExit(f"nothing to train: checkpoint already covers "
                         f"{start_epoch}/{args.epochs} epochs")
    t0 = time.time()
    state, hist = tr.train_epochs(ps, dd, state, remaining, log_every=1,
                                  start_epoch=start_epoch)
    dt = time.time() - t0
    acc = tr.evaluate(ps, dd, state)
    print(f"done: {remaining} epochs in {dt:.1f}s "
          f"({dt/remaining:.2f}s/epoch); test_acc={acc:.3f}")
    if rz is not None:
        print(f"resilience: faults_injected={len(rz.events)} "
              f"skipped_steps={rz.skipped_steps} "
              f"prefetch_retries="
              f"{int(obs.get().registry.value('prefetch_retries'))}")
        # flight paths print below via the health summary (finalize
        # routes FLIGHT_resilience.json through the health recorder)
    hs = health.summary()
    fmt = lambda v: "n/a" if v is None else f"{v:.3f}"
    print(f"health: halo skew={fmt(hs['skew'])} "
          f"edge-cut drift={fmt(hs['edge_cut_drift'])} "
          f"detections={len(hs['detections'])}")
    qs = quality.summary()
    if qs["audits_run"]:
        print(f"quality: audits={qs['audits_run']} "
              f"mean_err={fmt(qs['last_mean_err'])} "
              f"hidden_err={fmt(qs['last_hidden_err'])}")
    for p in hs["flight_paths"]:
        print(f"flight: {p}")
    if prom is not None:
        print(f"wrote {prom.write(obs.get().registry)}")
    for path in obs.flush():
        print(f"wrote {path}")
    if args.ckpt:
        checkpoint.save(args.ckpt, state["params"], int(state["step"]))
        print("saved", args.ckpt)


def run_lm(args):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.models.transformer import model as M
    from repro.train import lm_trainer
    from repro.train.optimizer import AdamConfig

    obs = _configure_obs(args)
    prom = _prom_writer(args, obs)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(jax.random.key(0), cfg)
    from repro.train.optimizer import adam_init
    opt = adam_init(params)
    step = jax.jit(lm_trainer.make_train_step(cfg, AdamConfig(lr=args.lr)))
    rng = jax.random.key(1)
    t0 = time.time()
    for i in range(args.steps):
        rng, k = jax.random.split(rng)
        tokens = jax.random.randint(k, (args.batch, args.seq), 0,
                                    cfg.vocab_size)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
        if cfg.num_patch_tokens:
            batch["patch_embeds"] = jnp.zeros(
                (args.batch, cfg.num_patch_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.is_encoder_decoder:
            batch["frame_embeds"] = jax.random.normal(
                k, (args.batch, cfg.num_frame_tokens, cfg.d_model)
            ).astype(jnp.bfloat16)
        with obs.span("lm_step", step=i):
            params, opt, metrics = step(params, opt, batch)
        obs.count("lm_tokens", args.batch * args.seq, subsystem="lm")
        if prom is not None:
            prom.maybe_write(obs.get().registry)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"step {i}: loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
    print(f"{args.steps} steps in {time.time()-t0:.1f}s")
    if prom is not None:
        print(f"wrote {prom.write(obs.get().registry)}")
    for path in obs.flush():
        print(f"wrote {path}")


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("gnn")
    g.add_argument("--model", default="graphsage",
                   choices=["graphsage", "gat"])
    g.add_argument("--mode", default="aep", choices=["aep", "sync", "drop"])
    g.add_argument("--ranks", type=int, default=4)
    g.add_argument("--vertices", type=int, default=20_000)
    g.add_argument("--degree", type=int, default=10)
    g.add_argument("--classes", type=int, default=16)
    g.add_argument("--feat-dim", type=int, default=64)
    g.add_argument("--hidden", type=int, default=128)
    g.add_argument("--layers", type=int, default=2,
                   help="GNN layers; --fanouts must list one per layer")
    g.add_argument("--fanouts", type=int, nargs="+", default=[5, 10])
    g.add_argument("--batch", type=int, default=256)
    g.add_argument("--epochs", type=int, default=5)
    g.add_argument("--lr", type=float, default=0.006)
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--hec-size", type=int, default=65536)
    g.add_argument("--hec-nc", type=int, default=512)
    g.add_argument("--hec-ls", type=int, default=2)
    g.add_argument("--hec-delay", type=int, default=1)
    g.add_argument("--ckpt", default=None)
    g.add_argument("--ckpt-dir", default=None, metavar="DIR",
                   help="stateful crash-resume: write a full training "
                        "checkpoint (params, opt, HEC, hot tier, inflight "
                        "pushes, RNG position) at epoch boundaries")
    g.add_argument("--ckpt-every", type=int, default=1, metavar="N",
                   help="checkpoint every N epochs (with --ckpt-dir)")
    g.add_argument("--ckpt-keep", type=int, default=3, metavar="K",
                   help="retain the newest K checkpoints (with --ckpt-dir)")
    g.add_argument("--resume", action="store_true",
                   help="restore the latest checkpoint in --ckpt-dir and "
                        "continue; the resumed run is bit-identical to one "
                        "that never crashed")
    g.add_argument("--fault-schedule", default=None, metavar="JSON",
                   help="deterministic fault injection: a JSON list of "
                        "{kind, epoch, step, rank} specs (kinds: nan_step, "
                        "drop_push, corrupt_push, delay_rank, kill_prefetch)")
    g.add_argument("--nan-guard", action="store_true",
                   help="skip minibatches whose loss/grads go non-finite "
                        "(counted as resilience_skipped_steps)")
    g.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of the phase spans")
    g.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the obs registry as JSONL")
    g.add_argument("--flight-dir", default=".", metavar="DIR",
                   help="where the health plane dumps FLIGHT_*.json on a "
                        "detection or an escaped exception")
    g.add_argument("--audit-interval", type=int, default=0, metavar="N",
                   help="run the exactness audit every N epochs (0 = off): "
                        "sampled cached embeddings vs offline recompute, "
                        "relative-L2 error histograms per layer")
    g.add_argument("--quality-budget", type=float, default=None,
                   metavar="ERR",
                   help="arm the quality-budget detector: audit mean error "
                        "persistently above ERR dumps FLIGHT_quality.json")
    g.add_argument("--prom-out", default=None, metavar="PATH",
                   help="periodically write the registry in Prometheus "
                        "text format (node-exporter textfile collector)")
    g.set_defaults(fn=run_gnn)

    l = sub.add_parser("lm")
    l.add_argument("--arch", required=True)
    l.add_argument("--reduced", action="store_true", default=True)
    l.add_argument("--steps", type=int, default=20)
    l.add_argument("--batch", type=int, default=4)
    l.add_argument("--seq", type=int, default=128)
    l.add_argument("--lr", type=float, default=3e-4)
    l.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of the lm_step "
                        "spans")
    l.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the obs registry as JSONL")
    l.add_argument("--prom-out", default=None, metavar="PATH",
                   help="periodically write the registry in Prometheus "
                        "text format (node-exporter textfile collector)")
    l.set_defaults(fn=run_lm)

    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
