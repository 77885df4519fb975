"""Serve kernel microbenchmarks (the PR 9 raw-speed pass).

Two kernel row families, each against the composed-jnp path it
replaces, plus the host fanout draw:

  * fused serve layer (``kernels/serve_fused.py``) vs the jit'd composed
    ``ref.serve_layer_ref`` — gather + masked mean + dense UPDATE in one
    dispatch.  In interpret mode the kernel body lowers to the same XLA
    ops as the composed path, so the honest expectation is parity; the
    SMOKE GATE therefore asserts the fused call is *not slower* (best
    paired-round speedup >= 1x), which still trips on any structural
    regression (gridded block copies, interpreter fallback) that would
    make the kernel 10-100x slower.
  * batched HEC probe (``hec_search_batched``) vs N single
    ``hec_search_kernel`` dispatches — one grid over all fused exchange
    rounds.  SMOKE GATE: one batched call beats N singles.
  * the host fanout draw ``_draw_neighbors`` (Floyd's algorithm).

All jitted paths take their operands as *arguments* — closing over
concrete arrays lets XLA constant-fold the gather at trace time and the
measurement collapses to a no-op.  Derived fields carry roofline
coordinates (flops, bytes, intensity) for ``make_roofline_md.py``; the
RESULT payload repeats the gate numbers machine-readably for CI.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, result, time_fn
from repro.cache import hec as hec_lib
from repro.kernels import ops, ref
from repro.pipeline.vectorized_sampler import _draw_neighbors

_GATE_ROUNDS = 8        # paired timing rounds for the smoke serve gate
_GATE_ITERS = 20        # iterations per round (min is taken)


@jax.jit
def _composed_layer(h, nbr, valid, wn, ws, b):
    return ref.serve_layer_ref({"wn": wn, "ws": ws, "b": b}, h, nbr, valid,
                               relu=True)


def _fused_layer(h, nbr, valid, wn, ws, b):
    return ops.fused_serve_layer(h, nbr, valid, wn, ws, b, relu=True)


def _serve_args(M, f, D, K, N, rng):
    return (jnp.asarray(rng.normal(size=(N, D)).astype(np.float32)),
            jnp.asarray(rng.integers(-1, N, size=(M, f)).astype(np.int32)),
            jnp.asarray(rng.random(N) > 0.1),
            jnp.asarray(rng.normal(size=(D, K)).astype(np.float32) * 0.1),
            jnp.asarray(rng.normal(size=(D, K)).astype(np.float32) * 0.1),
            jnp.zeros((K,), jnp.float32))


def _tmin(fn, args, iters):
    fn(*args).block_until_ready()
    best = np.inf
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _paired_speedup(args, rounds=_GATE_ROUNDS, iters=_GATE_ITERS):
    """Best composed/fused min-time ratio over interleaved rounds.

    Interleaving cancels machine drift; taking the best round asks "can
    the fused kernel match the composed path at all?" — robust to this
    container's ~10% scheduler noise while still failing by orders of
    magnitude on a real structural regression.
    """
    sps = []
    for _ in range(rounds):
        tc = _tmin(_composed_layer, args, iters)
        tf = _tmin(_fused_layer, args, iters)
        sps.append(tc / tf)
    return max(sps), float(np.median(sps))


def main(iters=8, smoke=False):
    rng = np.random.default_rng(7)

    # -- fused serve layer ------------------------------------------------
    serve_shapes = [(8192, 16, 128, 128, 16384, "full"),
                    (4096, 16, 128, 128, 8192, "mid")]
    if smoke:
        serve_shapes, iters = [(1024, 16, 64, 64, 2048, "smoke")], 4
    serve_res = {}
    for M, f, D, K, N, tag in serve_shapes:
        args = _serve_args(M, f, D, K, N, rng)
        t_comp = time_fn(_composed_layer, *args, iters=iters)
        t_fused = time_fn(_fused_layer, *args, iters=iters)
        sp = t_comp / t_fused
        # roofline coordinates: neighbor gather + masked mean + 2 matmuls
        flops = 2.0 * M * D * K * 2 + 3.0 * M * f * D
        bytes_ = 4.0 * (M * f * D + 2 * M * D + 2 * D * K + 2 * M * K)
        emit(f"serve_composed_{tag}", t_comp, "")
        emit(f"serve_fused_{tag}", t_fused,
             f"speedup={sp:.2f}x;flops={flops:.3g};bytes={bytes_:.3g};"
             f"intensity={flops / bytes_:.2f}")
        serve_res[tag] = {"composed_us": t_comp, "fused_us": t_fused,
                          "speedup": sp}
        if smoke:
            best, med = _paired_speedup(args)
            serve_res[tag]["gate_best_speedup"] = best
            serve_res[tag]["gate_median_speedup"] = med
            assert best >= 1.0, (
                f"SMOKE GATE: fused serve layer slower than composed jnp in "
                f"every paired round (best {best:.3f}x, median {med:.3f}x)")

    # -- batched HEC probe ------------------------------------------------
    nsets, ways, rounds, n = (512, 4, 4, 64) if smoke \
        else (4096, 8, 4, 512)
    state = hec_lib.hec_init(nsets * ways, ways, 16)
    vids = jnp.asarray(rng.integers(0, nsets * ways, size=2048)
                       .astype(np.int32))
    state = hec_lib.hec_store(
        state, vids, jnp.zeros((2048, 16), jnp.float32))
    probe2d = jnp.asarray(
        rng.integers(-1, nsets * ways, size=(rounds, n)).astype(np.int32))

    def singles(tags, probes):
        return [ops.hec_search_kernel(tags, probes[i])
                for i in range(rounds)]

    t_single = time_fn(singles, state.tags, probe2d, iters=iters)
    t_batched = time_fn(ops.hec_search_batched, state.tags, probe2d,
                        iters=iters)
    emit(f"probe_single_x{rounds}", t_single, "")
    emit(f"probe_batched_x{rounds}", t_batched,
         f"speedup={t_single / t_batched:.2f}x")
    if smoke:
        assert t_batched < t_single, (
            f"SMOKE GATE: batched probe ({t_batched:.1f}us) not faster "
            f"than {rounds} single probes ({t_single:.1f}us)")

    # -- host fanout draw ------------------------------------------------
    from repro.graph import partition_graph, synthetic_graph
    nv = 2000 if smoke else 50_000
    g = synthetic_graph(num_vertices=nv, avg_degree=12, num_classes=4,
                        feat_dim=8, seed=3)
    part = partition_graph(g, 1, seed=0).parts[0]
    n_cur = 512 if smoke else 4096
    fanout = 10
    cur = rng.integers(0, part.num_solid, size=n_cur).astype(np.int64)
    host_rng = np.random.default_rng(5)
    t_host = time_fn(
        lambda: _draw_neighbors(part.indptr, part.indices, cur,
                                part.num_solid, fanout, host_rng),
        iters=iters)
    emit("sample_host_np", t_host, "")

    result({"serve": serve_res,
            "probe": {"single_us": t_single, "batched_us": t_batched,
                      "rounds": rounds,
                      "speedup": t_single / t_batched},
            "sampler": {"host_us": t_host}})


if __name__ == "__main__":
    main()
