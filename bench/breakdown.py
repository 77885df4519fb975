"""Where one cell's traced window goes, by the program's own names.

    python bench/breakdown.py --workload sage-1chip --seed 7 --seconds 30

Runs the cell as ``bench/run.py --trace 1`` does (the same set-up, warm-up
and traced window, through ``core/session.py``) but without the check
against the reference, and prints one JSON line, per window step unless
named otherwise: the step loop's spans (``batch_wait_ms``,
``step_sync_ms``, ``epoch_fill_ms`` per epoch, ``loop_cover``), device
milliseconds by named scope (``scope_ms``, ``hec_device_ms``,
``agg_device_ms``, ``unattributed``), device idle time under the loop's
waits (``idle_on_batch_ms``), and the harness's own ``device_idle_share``
and ``host_prep_ms`` beside them (reductions in ``core/scopes.py`` and
``core/trace.py``).  ``--hlo-out PATH`` keeps the compiled step's HLO
text, gzipped.  It needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
# the TPU runtime's logs stay in the checkout, as in run.py
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(BENCH, ".cache", "tpu_logs"))


def capture_step() -> dict:
    """From here on, every step ``DistTrainer.make_step`` builds keeps its
    jitted program and the shapes and shardings of its first call's
    arguments in the returned dict, so that its compiled HLO text can be
    had after the run (``compiled_text``)."""
    import jax
    from repro.train.gnn_trainer import DistTrainer

    kept = {}
    make = DistTrainer.make_step

    def make_step(self, *args, **kwargs):
        jitted = make(self, *args, **kwargs)

        def step(*xs):
            if "args" not in kept:
                kept["jitted"] = jitted
                kept["args"] = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding), xs)
            return jitted(*xs)
        return step

    DistTrainer.make_step = make_step
    return kept


def compiled_text(kept: dict) -> str:
    """The HLO text of the captured step as compiled for the device (from
    the persistent compilation cache where the run filled it)."""
    return kept["jitted"].lower(*kept["args"]).compile().as_text()


def traced_window(cfg, graph, ps, chips: int, seed: int, seconds: float,
                  trace_dir: str):
    """``session.run_cell`` with a traced window; returns the run and the
    compiled step's HLO text."""
    from core import device as dev
    from core import session
    kept = capture_step()
    run, _ = session.run_cell(cfg, graph, ps, chips, seed, seconds,
                              time.perf_counter(), dev.CompileClock(),
                              trace_dir=trace_dir)
    return run, compiled_text(kept)


def readings(run, tr: dict, sc: dict) -> dict:
    """The result line's numbers from the run and both reductions."""
    steps, epochs = max(run.steps, 1), max(run.epochs, 1)
    per_step = lambda s: 1e3 * s / steps
    scope_ms = {k: per_step(v) for k, v in sc["scope_s"].items()}
    return {
        "epoch_s": run.window_s / epochs, "epochs": run.epochs,
        "steps": run.steps,
        "batch_wait_ms": per_step(sc["loop_s"]["batch_wait"]),
        "epoch_fill_ms": 1e3 * sc["loop_s"]["epoch_fill"] / epochs,
        "step_ms": per_step(sc["loop_s"]["step"]),
        "step_sync_ms": per_step(sc["loop_s"]["step_sync"]),
        "epoch_end_ms": 1e3 * sc["loop_s"]["epoch_end"] / epochs,
        "loop_cover": sc["loop_cover"], "loop_calls": sc["loop_calls"],
        "hec_device_ms": scope_ms.get("hec_lookup", 0.0)
        + scope_ms.get("hec_store", 0.0),
        "agg_device_ms": sum(v for k, v in scope_ms.items()
                             if k.endswith("_aggregate")),
        "idle_on_batch_ms": per_step(sc["idle_on_batch_s"]),
        "unattributed": sc["unattributed"], "scope_ms": scope_ms,
        "unattributed_ms": [[k, per_step(v)]
                            for k, v in sc["unattributed_ops"]],
        "device_busy_ms": per_step(tr["busy_s"]),
        "device_idle_share": 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]),
        "host_prep_ms": per_step(sum(run.phase_s.get(p, 0.0) for p in
                                     ("sample", "host_prep", "stage"))),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--hlo-out", default="",
                    help="file to keep the compiled step's HLO text in")
    args = ap.parse_args(argv)

    from run import resolve_cell
    from core import device as dev
    from core import scopes, session, trace

    cell = resolve_cell(args.workload)
    device = dev.require_chips(cell["chips"])
    dev.set_compile_cache(os.path.join(session.CACHE, "jax"))
    graph = session.load_graph(cell["traffic"])
    ps = session.load_partition(graph, cell["traffic"])
    cfg = session.build_config(cell["config"])
    trace_dir = os.path.join(session.CACHE, "breakdown", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    run, hlo = traced_window(cfg, graph, ps, cell["chips"], args.seed,
                             args.seconds, trace_dir)
    out = readings(run, trace.reduce(trace_dir, cell["chips"]),
                   scopes.reduce(trace_dir, cell["chips"], hlo))
    if args.hlo_out:
        with gzip.open(args.hlo_out, "wt") as f:
            f.write(hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **out, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
