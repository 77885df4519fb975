"""One run of one benchmark cell on the chip.

    python bench/run.py --workload sage-1chip --seed 7 --seconds 30 --trace 0

Loads the cell named in ``BENCHMARK.json`` (its configuration file under
``bench/configs/``, its model's maths under ``bench/models/``, its
traffic file under ``bench/traffic/`` and its limits under
``bench/workloads/``), sets up and warms up the program,
trains whole epochs for ``--seconds``, checks the first recorded steps
against the plain reference, and prints one JSON line last on stdout:
``correct``, ``attempted``/``failed`` (window steps / steps of epochs
whose loss was not finite), ``metrics``, ``device`` and, last, ``check``
(each compared number with its limit; also the last lines on stderr).
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics, each
read by ``bench/metrics/<name>.py``, with a ``breakdown``.

Without a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside ``bench/``, it exits non-zero and prints no
result.  JAX's compilation cache lives in
``bench/.cache/jax`` and the cell's graph and partition in
``bench/.cache`` (built by a cell's first run in a checkout).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
SRC = os.path.join(CHECKOUT, "src")
if SRC not in sys.path:
    sys.path.insert(1, SRC)
# the TPU runtime's logs stay in the checkout, not in a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".cache", "tpu_logs"))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(name: str, root: str = CHECKOUT) -> dict:
    """Everything one cell needs, found by name: its ``BENCHMARK.json``
    entry, configuration, traffic, limits and the metrics it reports."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    bdir = os.path.join(root, "bench")
    traffic = _load_json(os.path.join(bdir, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(bdir, "workloads", name + ".json"))

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    e2e = mine(bench["end_to_end"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in mine(bench["per_layer"])
                 if m["moves"] in e2e_names]
    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": traffic, "limits": limits["limits"],
            "end_to_end": e2e, "per_layer": per_layer, "bench": bdir}


def load_metric(name: str, bdir: str):
    """The reader ``bench/metrics/<name>.py``; its ``read(record)`` returns
    the metric's value, or None where the run has nothing to read."""
    path = os.path.join(bdir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(specs, record: dict, bdir: str) -> dict:
    out = {}
    for m in specs:
        v = load_metric(m["name"], bdir).read(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve_cell(args.workload)
    try:
        import repro.launch.train  # noqa: F401  the program under test
    except ImportError as e:
        raise SystemExit(f"bench: the program is not in {SRC}: {e}")
    from core import check, session
    from core import device as dev
    from core import trace as trace_lib
    from core.flops import step_flops
    from core.reference import Model

    config, traffic = cell["config"], cell["traffic"]
    c = config["config"]
    m = Model.from_config(c)
    device = dev.require_chips(cell["chips"])
    peaks = dev.peaks_for(device["kind"], os.path.join(BENCH, "peaks.json"))
    dev.set_compile_cache(os.path.join(session.CACHE, "jax"))
    clock = dev.CompileClock()

    graph = session.load_graph(traffic)
    ps = session.load_partition(graph, traffic)
    cfg = session.build_config(config)
    trace_dir = ""
    if args.trace:
        trace_dir = os.path.join(session.CACHE, "trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run, rec = session.run_cell(cfg, graph, ps, cell["chips"], args.seed,
                                args.seconds, T_START, clock,
                                trace_dir=trace_dir)

    record = {"run": run, "chips": cell["chips"], "peaks": peaks,
              "flops_per_step": step_flops(m)["total"], "trace": None}
    breakdown = None
    if trace_dir:
        record["trace"] = trace_lib.reduce(trace_dir, cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        breakdown = trace_lib.breakdown(record["trace"])
    specs = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = read_metrics(specs, record, cell["bench"])

    clock.live = False
    numbers = check.Check(rec, ps, graph, m, c["hec"], args.seed).program()
    correct, rows = check.judge(numbers, cell["limits"])

    per_epoch = run.steps / max(run.epochs, 1)
    failed = int(round(per_epoch * sum(not math.isfinite(x)
                                       for x in run.losses)))
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if record["trace"] is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    log = lambda s: print(s, file=sys.stderr, flush=True)
    log(f"bench: {args.workload} seed={args.seed} setup_s={run.setup_s:.3f} "
        f"compile_s={run.compile_s:.3f} ({run.compiles} compiles) "
        f"window_s={run.window_s:.3f} epochs={run.epochs} steps={run.steps} "
        f"compiles_in_window={run.compiles_in_window} "
        f"({run.compile_in_window_s:.3f} s) "
        f"hit_rate_l0={run.hit_rate_l0:.4f} push_rows={run.push_rows:.0f} "
        f"peak_bytes={run.memory_peak_bytes} losses={run.losses} "
        f"epoch_walls={run.epoch_walls}")
    log(f"bench: recorded losses={rec.losses} correct={correct}")
    for name, v, lim in rows:
        log(f"check {name} {v!r} limit {lim!r}")
    result = {"correct": correct, "attempted": run.steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in rows}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
