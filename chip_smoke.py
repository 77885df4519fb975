"""Chip smoke test: the paper's GraphSAGE trained end to end on a TPU.

    python chip_smoke.py            # one chip: two epochs of training
    python chip_smoke.py --chips 4  # four chips: AEP overlap vs inline push

One chip.  A seeded synthetic graph (200k vertices, 128-wide features, 172
classes, average degree 15; about 20 steps per epoch) goes through the
launcher's own path -- ``partition_graph``, ``setup_gnn`` (the per-rank
tables, mesh, ``DistTrainer(mode="aep")`` and state that ``launch/train.py
gnn`` builds), ``train_epochs`` and ``evaluate`` -- with
``GRAPHSAGE_PAPERS100M`` exactly as published: 3 layers, hidden 256,
fanouts 5/10/15, batch 1000 and an HEC of 1M entries per layer.  The
weights are random from ``--seed``.  It fails unless every epoch's loss is
finite, epoch 2's loss is below epoch 1's and test accuracy beats chance.

Four chips.  The same model and graph with four ranks, one per chip, train
the same epoch twice: with the AEP all_to_all dispatched between the
forward and backward passes (``overlap=True``) and inline after the
backward (``overlap=False``).  Each run must serve some layer-0 halos
from its HEC (the push delivered).  The final params and HECs must be
bit-identical; where floats differ, the largest relative difference
(max |a - b| / max |a| per array) must stay under 1e-5 and integer arrays
must still match exactly.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU, or away from the repository's ``src``, it exits non-zero and prints no
such line.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GRAPH = dict(num_vertices=200_000, avg_degree=15, num_classes=172,
             feat_dim=128)
MAX_REL_DIFF = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent compiling (XLA backend compiles, from
    ``jax.monitoring``) since construction."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs


def tpu_devices(chips: int) -> dict:
    """The device record of the last line; exits unless JAX found
    ``chips`` TPU devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU device(s); JAX found "
                 f"{len(devs)} {devs[0].platform}: {devs}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def train_one_chip(cfg, graph: dict, seed: int) -> None:
    """Two epochs through the launcher's path on one rank; raises on a
    non-finite or non-falling loss, or chance-level test accuracy."""
    import jax
    from repro.graph import partition_graph, synthetic_graph
    from repro.launch.train import setup_gnn

    clock = CompileClock()
    t0 = time.perf_counter()
    g = synthetic_graph(seed=seed, **graph)
    ps = partition_graph(g, 1, seed=seed)
    dd, tr, state = setup_gnn(ps, cfg, seed=seed, mode="aep")
    jax.block_until_ready(state)
    log(f"setup: V={g.num_vertices} E={g.num_edges} "
        f"train={int(g.train_mask.sum())} in "
        f"{time.perf_counter() - t0:.1f}s")

    hist, step_fn = [], tr.make_step(dd)
    for epoch in range(2):
        step0, c0 = int(state["step"]), clock.seconds
        t = time.perf_counter()
        state, h = tr.train_epochs(ps, dd, state, 1, step_fn=step_fn,
                                   start_epoch=epoch)
        jax.block_until_ready(state)
        wall = time.perf_counter() - t
        steps = int(state["step"]) - step0
        hist += h
        log(f"epoch {epoch + 1}: steps={steps} wall_s={wall:.3f} "
            f"compile_s={clock.seconds - c0:.3f} "
            f"s_per_step={wall / max(steps, 1):.4f} "
            f"loss={h[-1]['loss']:.4f} acc={h[-1]['acc']:.4f}")
    c0 = clock.seconds
    acc = tr.evaluate(ps, dd, state)
    log(f"evaluate: test_acc={acc:.4f} compile_s={clock.seconds - c0:.3f}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")

    losses = [h["loss"] for h in hist]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if not acc > 1.0 / cfg.num_classes:
        raise AssertionError(f"test accuracy {acc} is not above chance "
                             f"1/{cfg.num_classes}")


def max_rel_diff(a, b) -> float:
    """max |a - b| / max |a| of one array pair (0.0 when bit-identical)."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if np.array_equal(a, b):
        return 0.0
    if not np.issubdtype(a.dtype, np.floating):
        return math.inf                    # tags, ages: exact or wrong
    scale = float(np.max(np.abs(a)))
    d = float(np.max(np.abs(a - b))) / max(scale, 1e-30)
    return d if math.isfinite(d) else math.inf


def overlap_vs_inline(cfg, graph: dict, seed: int, ranks: int) -> None:
    """One epoch with the AEP push overlapped, one inline, same steps;
    raises unless final params and HECs agree (see module doc)."""
    import jax
    from repro.graph import partition_graph, synthetic_graph
    from repro.launch.train import setup_gnn

    clock = CompileClock()
    g = synthetic_graph(seed=seed, **graph)
    t0 = time.perf_counter()
    ps = partition_graph(g, ranks, seed=seed)
    log(f"partitioned V={g.num_vertices} into {ranks}: "
        f"edge-cut={ps.edge_cut_frac:.3f} in {time.perf_counter() - t0:.1f}s")
    finals = {}
    for overlap in (True, False):
        dd, tr, state = setup_gnn(ps, cfg, seed=seed, mode="aep",
                                  overlap=overlap)
        c0, t = clock.seconds, time.perf_counter()
        state, hist = tr.train_epochs(ps, dd, state, 1)
        jax.block_until_ready(state)
        loss, hits = hist[-1]["loss"], hist[-1].get("hec_hit_rate_l0", 0.0)
        log(f"overlap={overlap}: steps={int(state['step'])} "
            f"wall_s={time.perf_counter() - t:.3f} "
            f"compile_s={clock.seconds - c0:.3f} "
            f"loss={loss:.4f} hec_hit_rate_l0={hits:.4f}")
        shards = [d.memory_stats() or {} for d in jax.devices()[:ranks]]
        log(f"peak_bytes_in_use per chip="
            f"{[s.get('peak_bytes_in_use', 'n/a') for s in shards]}")
        if not math.isfinite(loss):
            raise AssertionError(f"overlap={overlap}: non-finite loss")
        if not hits > 0.0:
            raise AssertionError(f"overlap={overlap}: no halo was served "
                                 f"from the HEC; the AEP push delivered "
                                 f"nothing usable")
        finals[overlap] = jax.device_get(
            {"params": state["params"], "hec": state["hec"]})
        del dd, tr, state

    leaves = jax.tree_util.tree_leaves_with_path(finals[True])
    other = jax.tree_util.tree_leaves(finals[False])
    diffs = {jax.tree_util.keystr(p): max_rel_diff(a, b)
             for (p, a), b in zip(leaves, other)}
    worst = max(diffs, key=diffs.get)
    identical = sum(d == 0.0 for d in diffs.values())
    log(f"overlap vs inline: {identical}/{len(diffs)} arrays bit-identical; "
        f"max_rel_diff={diffs[worst]:.3e} at {worst}")
    if not diffs[worst] < MAX_REL_DIFF:
        raise AssertionError(f"overlap and inline AEP disagree: "
                             f"{diffs[worst]} at {worst}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train on one chip; 4: AEP overlap vs inline "
                         "on a four-chip mesh (that phase alone)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs.gnn import GRAPHSAGE_PAPERS100M
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    device = tpu_devices(args.chips)
    log(f"device: {device} compile_cache={cache} "
        f"({'warm' if warm else 'cold'})")
    if args.chips == 1:
        train_one_chip(GRAPHSAGE_PAPERS100M, GRAPH, args.seed)
    else:
        overlap_vs_inline(GRAPHSAGE_PAPERS100M, GRAPH, args.seed, ranks=4)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
