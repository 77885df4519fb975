"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  fig2   bench_update       single-socket fused UPDATE (paper Fig. 2)
  fig3/4 bench_scaling      epoch time/speedup vs ranks (Figs. 3 & 4)
  fig5   bench_distdgl      DistGNN-MB vs DistDGL-like baseline (Fig. 5)
  hec    bench_hec          HEC hit-rates (paper §4.4)
  comm   bench_comm         exchange plans + fused/overlapped AEP push
  table3 bench_convergence  convergence parity (Table 3 / §4.5)
  pipeline bench_pipeline   vectorized sampler + async prefetch (§3.3/§3.4)
  gnn_serve bench_gnn_serve inference serving: cold vs pre-warmed cache
  gnn_serve_dist bench_gnn_serve_dist sharded serving: shard scaling + halo cache
  roofline                   dry-run roofline table (deliverable g)
  obs    bench_obs          tracing overhead gate (<10%) + TRACE_obs.json
  quality bench_quality     staleness sweep: epoch time vs accuracy vs audit err
  kernels bench_kernels     fused serve / batched probe kernels, host draw
  resilience bench_resilience ckpt save/restore, degraded serving, recovery

``--smoke`` runs every registered benchmark at tiny scale (a CI bit-rot
guard: each suite must still execute end-to-end, numbers are meaningless —
except the perf *gates* individual suites assert even at tiny scale, e.g.
hot-tier modeled remote rows < tier-disabled).  Each suite's rows and
RESULT payload are additionally written as ``BENCH_<suite>.json`` under
``--out-dir`` (default ``$BENCH_OUT_DIR`` or ``bench_results``) so the
perf trajectory is machine-readable across PRs; CI uploads them as a
workflow artifact.
"""
from __future__ import annotations

import argparse
import os
import traceback

from benchmarks import common


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("only", nargs="?", default=None,
                    help="run only suites whose name contains this")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale pass over every suite (CI)")
    ap.add_argument("--out-dir",
                    default=os.environ.get("BENCH_OUT_DIR", "bench_results"),
                    help="directory for BENCH_<suite>.json artifacts")
    args = ap.parse_args()
    common.set_out_dir(args.out_dir)
    from benchmarks import (bench_comm, bench_convergence, bench_distdgl,
                            bench_gnn_serve, bench_gnn_serve_dist, bench_hec,
                            bench_kernels, bench_obs, bench_pipeline,
                            bench_quality, bench_resilience, bench_scaling,
                            bench_update, roofline)
    suites = {
        "fig2_update": bench_update.main,
        "fig3_fig4_scaling": bench_scaling.main,
        "fig5_distdgl": bench_distdgl.main,
        "hec_hitrates": bench_hec.main,
        "comm": bench_comm.main,
        "table3_convergence": bench_convergence.main,
        "pipeline": bench_pipeline.main,
        "gnn_serve": bench_gnn_serve.main,
        "gnn_serve_dist": bench_gnn_serve_dist.main,
        "roofline": roofline.main,
        "obs": bench_obs.main,
        "quality": bench_quality.main,
        "kernels": bench_kernels.main,
        "resilience": bench_resilience.main,
    }
    print("name,us_per_call,derived")
    try:
        for name, fn in suites.items():
            if args.only and args.only not in name:
                continue
            common.begin_suite(name)
            try:
                fn(smoke=args.smoke)
            except Exception as e:
                traceback.print_exc()
                print(f"{name},0.0,ERROR={type(e).__name__}")
                raise SystemExit(1)
    finally:
        for path in common.write_artifacts(args.out_dir):
            print(f"artifact: {path}")


if __name__ == "__main__":
    main()
