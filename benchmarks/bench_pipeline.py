"""Asynchronous minibatch pipeline benchmark (paper §3.3/§3.4 overlap).

Two measurements on the synthetic OGBN-like graph:
  * sampler-only throughput: reference per-row-loop ``sample_blocks`` vs
    the vectorized CSR sampler (acceptance bar: >=5x),
  * end-to-end epoch time of ``DistTrainer.train_epochs``: inline
    sampling (0 workers) vs the async pipeline (one prefetch worker),
    both with double-buffered staging.

Emits the usual ``name,us_per_call,derived`` CSV rows plus one
``RESULT{...}`` JSON line with the raw numbers.
"""
from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.common import emit


def bench_sampler(part, batch_size=1000, fanouts=(5, 10, 15), iters=5):
    from repro.graph.sampling import epoch_minibatches, sample_blocks
    from repro.pipeline import sample_blocks_vectorized

    rng = np.random.default_rng(0)
    seeds = epoch_minibatches(part, batch_size, rng)[0]

    def run(fn, n):
        fn(part, seeds, fanouts, rng, batch_size)       # warmup
        t0 = time.perf_counter()
        for _ in range(n):
            fn(part, seeds, fanouts, rng, batch_size)
        return (time.perf_counter() - t0) / n

    t_ref = run(sample_blocks, iters)
    t_vec = run(sample_blocks_vectorized, 4 * iters)
    speedup = t_ref / t_vec
    emit("pipeline_sampler_reference", t_ref * 1e6, "")
    emit("pipeline_sampler_vectorized", t_vec * 1e6,
         f"speedup={speedup:.1f}x")
    return {"sampler_ref_us": t_ref * 1e6, "sampler_vec_us": t_vec * 1e6,
            "sampler_speedup": speedup}


def bench_epoch(ps, epochs=2):
    import jax
    from repro.configs.gnn import PipelineConfig, small_gnn_config
    from repro.train.gnn_trainer import DistTrainer, build_dist_data

    mesh = jax.make_mesh((1,), ("data",))

    def run(pipe_cfg):
        cfg = small_gnn_config("graphsage", batch_size=512, feat_dim=32,
                               num_classes=16, fanouts=(5, 10),
                               hidden_size=64, pipeline=pipe_cfg)
        dd = build_dist_data(ps, cfg)
        tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=1, mode="aep")
        state = tr.init_state(jax.random.key(0))
        step_fn = tr.make_step(dd)
        # warmup epoch compiles the step and pre-touches caches
        state, _ = tr.train_epochs(ps, dd, state, 1, step_fn=step_fn)
        t0 = time.perf_counter()
        state, hist = tr.train_epochs(ps, dd, state, epochs, step_fn=step_fn)
        return (time.perf_counter() - t0) / epochs, hist

    t_sync, h_sync = run(PipelineConfig(num_workers=0))     # inline
    t_async, h_async = run(PipelineConfig(num_workers=1, prefetch_depth=1))

    # worker count must not change the training trajectory (bit-identical)
    drift = max(abs(a["loss"] - b["loss"])
                for a, b in zip(h_sync, h_async))
    emit("pipeline_epoch_vectorized_sync", t_sync * 1e6, "")
    # NB on a host-only CPU backend sampling threads share cores with XLA,
    # so async ~= sync here; the overlap pays off when the device is real.
    emit("pipeline_epoch_async", t_async * 1e6,
         f"speedup={t_sync/t_async:.2f}x;loss_drift={drift:.1e}")
    return {"epoch_sync_us": t_sync * 1e6, "epoch_async_us": t_async * 1e6,
            "loss_drift": drift}


def main(smoke=False):
    from repro.graph import partition_graph, synthetic_graph

    g = synthetic_graph(num_vertices=4000 if smoke else 30_000,
                        avg_degree=10, num_classes=16, feat_dim=32, seed=0)
    ps = partition_graph(g, 1, seed=0)
    out = bench_sampler(ps.parts[0], batch_size=256 if smoke else 1000,
                        iters=2 if smoke else 5)
    out.update(bench_epoch(ps, epochs=1 if smoke else 2))
    print("RESULT" + json.dumps(out))


if __name__ == "__main__":
    main()
