"""Record the small chip trace that ``test_bench_scopes.py`` reduces.

    python bench/tests/record_scopes.py

Trains the test-size GraphSAGE of ``record_trace.py`` on one TPU chip and
traces a short window (through ``bench/breakdown.py``'s
``traced_window``) into ``bench/tests/data/1_chip_scopes/``: the
``.xplane.pb`` and the compiled step's HLO text, ``step.hlo.txt.gz``.
"""
from __future__ import annotations

import gzip
import os
import shutil
import sys


def _quoted(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n") + '"'


def trim(path: str) -> None:
    """Rewrite the trace at ``path`` with each device's op line and the
    host lines that hold the program's spans: event names and times, no
    stats (``core/scopes.py`` and ``core/trace.py`` read nothing else)."""
    from jax.profiler import ProfileData
    from core import scopes, trace
    spans = {trace.WINDOW, *scopes.LOOP_SPANS}
    out = []
    for pid, plane in enumerate(ProfileData.from_file(path).planes):
        if trace._device_index(plane.name) is not None:
            lines = [ln for ln in plane.lines if ln.name in trace.OPS_LINES]
        elif plane.name.startswith("/host:"):
            lines = [ln for ln in plane.lines
                     if any(ev.name in spans for ev in ln.events)]
        else:
            continue
        ids = {}
        text = [f"planes {{ id: {pid + 1} name: {_quoted(plane.name)}"]
        for k, ln in enumerate(lines):
            text.append(f"lines {{ id: {k + 1} name: {_quoted(ln.name)} "
                        "timestamp_ns: 0")
            for ev in ln.events:
                i = ids.setdefault(ev.name, len(ids) + 1)
                text.append(f"events {{ metadata_id: {i} offset_ps: "
                            f"{round(ev.start_ns * 1000)} duration_ps: "
                            f"{round(ev.duration_ns * 1000)} }}")
            text.append("}")
        for name, i in ids.items():
            text.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {_quoted(name)} }} }}")
        out.append("\n".join(text) + "\n}")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(path, "wb") as f:
        f.write(blob)


def main():
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.dirname(here)
    sys.path.insert(0, bench)
    sys.path.insert(1, os.path.join(os.path.dirname(bench), "src"))
    from breakdown import traced_window
    from core import device, session
    from repro.configs.gnn import HECConfig, small_gnn_config

    device.require_chips(1)
    out = os.path.join(here, "data", "1_chip_scopes")
    shutil.rmtree(out, ignore_errors=True)
    traffic = dict(num_vertices=20000, avg_degree=10, num_classes=8,
                   feat_dim=32, train_frac=0.1, graph_seed=0, parts=1)
    cache = os.path.join(session.CACHE, "test")
    g = session.load_graph(traffic, cache)
    ps = session.load_partition(g, traffic, cache)
    cfg = small_gnn_config("graphsage", hec=HECConfig(
        cache_size=65536, ways=8, life_span=2, push_limit=256, delay=1))
    _, hlo = traced_window(cfg, g, ps, 1, 5, 0.3, out)
    for root, _, files in os.walk(out):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".xplane.pb"):
                trim(path)
            else:
                os.remove(path)
    with gzip.open(os.path.join(out, "step.hlo.txt.gz"), "wt") as f:
        f.write(hlo)
    for root, _, files in os.walk(out):
        for name in files:
            path = os.path.join(root, name)
            print(path, os.path.getsize(path))


if __name__ == "__main__":
    main()
