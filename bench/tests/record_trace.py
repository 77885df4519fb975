"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python bench/tests/record_trace.py [chips]     # 1 or 4 (default) TPU chips

Trains a test-size GraphSAGE with one rank per chip and traces a short
window (wrapped in the ``bench_window`` annotation, as the harness does)
into ``bench/tests/data/<chips>_chips/``, keeping only the
``.xplane.pb``.
"""
from __future__ import annotations

import os
import shutil
import sys
import time


def main():
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.dirname(here)
    sys.path.insert(0, bench)
    sys.path.insert(1, os.path.join(os.path.dirname(bench), "src"))
    from core import device, session
    from repro.configs.gnn import HECConfig, small_gnn_config

    chips = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    device.require_chips(chips)
    out = os.path.join(here, "data", f"{chips}_chips")
    shutil.rmtree(out, ignore_errors=True)
    traffic = dict(num_vertices=20000, avg_degree=10, num_classes=8,
                   feat_dim=32, train_frac=0.1, graph_seed=0, parts=chips)
    cache = os.path.join(session.CACHE, "test")
    g = session.load_graph(traffic, cache)
    ps = session.load_partition(g, traffic, cache)
    cfg = small_gnn_config("graphsage", hec=HECConfig(
        cache_size=65536, ways=8, life_span=2, push_limit=256, delay=1))
    session.run_cell(cfg, g, ps, chips, 5, 0.3, time.perf_counter(),
                     device.CompileClock(), trace_dir=out)
    for root, _, files in os.walk(out):
        for name in files:
            path = os.path.join(root, name)
            if not name.endswith(".xplane.pb"):
                os.remove(path)
            else:
                print(path, os.path.getsize(path))


if __name__ == "__main__":
    main()
