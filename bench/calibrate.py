"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload sage-1chip --seeds 1,2,3 --stand-ins 3

In one process (the graph, partition and compiled programs are shared),
for each seed: ``setup_gnn``, the warm-up epoch with its first three steps
recorded through the timed step, and the comparison with the float32
reference -- the program's reading.  For the first ``--stand-ins`` seeds
also the readings of the stand-ins put in the program's place: the
control (the reference in bfloat16), the reference with half of each
batch left out, and, on several chips, with the exchange between chips
left out.  Each reading is printed on stdout as soon as it exists, one
JSON line each: the seed's program line, then one line per stand-in
(``{"seed", "stand_in", "numbers", "seconds"}``), so that a call cut by
its time limit keeps what it read.  Benchmark runs never run this; it
needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--stand-ins", type=int, default=3)
    args = ap.parse_args(argv)

    from run import resolve_cell
    from core import check, session
    from core import device as dev
    from core.reference import Model

    cell = resolve_cell(args.workload)
    dev.require_chips(cell["chips"])
    dev.set_compile_cache(os.path.join(session.CACHE, "jax"))
    clock = dev.CompileClock()
    graph = session.load_graph(cell["traffic"])
    ps = session.load_partition(graph, cell["traffic"])
    cfg = session.build_config(cell["config"])
    c = cell["config"]["config"]
    m = Model.from_config(c)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run, rec = session.run_cell(cfg, graph, ps, cell["chips"], seed, 0,
                                    t0, clock)
        t1 = time.perf_counter()
        chk = check.Check(rec, ps, graph, m, c["hec"], seed)
        print(json.dumps({
            "seed": seed, "program": chk.program(), "setup_s": run.setup_s,
            "reference_s": time.perf_counter() - t1,
            "peak_bytes": run.memory_peak_bytes, "losses": rec.losses,
            "ref_losses": chk.res.losses}), flush=True)
        if i >= args.stand_ins:
            continue
        stand_ins = [chk.control, chk.half_batch]
        if cell["chips"] > 1:
            stand_ins.append(chk.no_exchange)
        for stand_in in stand_ins:
            t2 = time.perf_counter()
            numbers = stand_in()
            print(json.dumps({"seed": seed, "stand_in": stand_in.__name__,
                              "numbers": numbers,
                              "seconds": time.perf_counter() - t2}),
                  flush=True)


if __name__ == "__main__":
    main()
