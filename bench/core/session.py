"""Drives the program under test for one cell.

The graph and its partition come from the checkout's cache (built on the
cell's first run), then the program's normal path: ``setup_gnn(ps, cfg,
mode="aep")`` builds the per-rank tables, mesh, trainer and state, and
``DistTrainer.train_epochs`` trains with the default pipeline and
``overlap=True``.  The warm-up epoch compiles the step (from the
persistent cache after a cell's first run) and fills the pipeline and,
on several chips, the HEC; a :class:`StepRecorder` wrapped round the
compiled step keeps host copies of what the reference needs from its
first three calls.  The window then trains whole epochs through the same
compiled step until the run's seconds have passed.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import inspect
import json
import os
import pickle
import time
from typing import List

import numpy as np

from core import graphgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


# ---------------------------------------------------------------------------
# graph and partition, kept in the checkout's cache
# ---------------------------------------------------------------------------
GRAPH_KEYS = ("num_vertices", "avg_degree", "num_classes", "feat_dim",
              "train_frac", "graph_seed")
GRAPH_ARRAYS = ("indptr", "indices", "features", "labels", "train_mask",
                "test_mask")


def _digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p.encode() if isinstance(p, str) else p)
    return h.hexdigest()[:16]


def graph_key(traffic: dict) -> str:
    params = {k: traffic[k] for k in GRAPH_KEYS}
    return _digest(json.dumps(params, sort_keys=True),
                   inspect.getsource(graphgen))


def build_graph(traffic: dict) -> graphgen.Graph:
    return graphgen.synthetic_graph(
        num_vertices=traffic["num_vertices"],
        avg_degree=traffic["avg_degree"],
        num_classes=traffic["num_classes"], feat_dim=traffic["feat_dim"],
        train_frac=traffic["train_frac"], seed=traffic["graph_seed"])


def _publish(tmp: str, final: str) -> None:
    """Make a finished cache entry visible in one rename."""
    if os.path.exists(final):
        return
    os.replace(tmp, final)


def load_graph(traffic: dict, cache: str = CACHE) -> graphgen.Graph:
    """The cell's graph: built once per checkout, then memory-mapped."""
    d = os.path.join(cache, "graph-" + graph_key(traffic))
    if not os.path.isdir(d):
        g = build_graph(traffic)
        tmp = f"{d}.tmp{os.getpid()}"
        os.makedirs(tmp, exist_ok=True)
        for name in GRAPH_ARRAYS:
            np.save(os.path.join(tmp, name + ".npy"), getattr(g, name))
        _publish(tmp, d)
    return graphgen.Graph(**{
        name: np.load(os.path.join(d, name + ".npy"), mmap_mode="r")
        for name in GRAPH_ARRAYS})


def load_partition(g, traffic: dict, cache: str = CACHE):
    """The program's own partitioning of the graph into the traffic's
    ``parts``, kept per checkout and keyed by the partitioner's source."""
    from repro.graph import partition as part_mod
    parts = traffic["parts"]
    key = _digest(graph_key(traffic), str(parts),
                  inspect.getsource(part_mod))
    path = os.path.join(cache, f"partition-{key}.pkl")
    if not os.path.exists(path):
        ps = part_mod.partition_graph(g, parts, seed=traffic["graph_seed"])
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(ps, f, protocol=pickle.HIGHEST_PROTOCOL)
        _publish(tmp, path)
    with open(path, "rb") as f:
        return pickle.load(f)


# ---------------------------------------------------------------------------
# the configuration as run
# ---------------------------------------------------------------------------
def build_config(config: dict):
    """The program's preset with the file's overrides; raises unless every
    number the file states under ``config`` is what will run."""
    from repro.configs import gnn as presets
    cfg = getattr(presets, config["preset"])
    over = dict(config.get("overrides", {}))
    hec = dataclasses.replace(cfg.hec, **over.pop("hec", {}))
    cfg = dataclasses.replace(cfg, hec=hec, **over)
    ran = dataclasses.asdict(cfg)
    ran["fanouts"] = list(ran["fanouts"])
    for key, want in config["config"].items():
        got = ran[key]
        if isinstance(want, dict):
            got = {k: got[k] for k in want}
        if got != want:
            raise ValueError(f"{config['name']}: {key} runs as {got}, the "
                             f"configuration file says {want}")
    return cfg


# ---------------------------------------------------------------------------
# recording the first steps
# ---------------------------------------------------------------------------
def read_cache(hec_states) -> List[list]:
    """Per layer, per rank: (vids, rows) of the HEC's valid lines."""
    import jax
    out = []
    for st in hec_states:
        tags = np.asarray(jax.device_get(st.tags))
        per_rank = []
        shards = {s.index[0].start or 0: s for s in st.values.addressable_shards}
        for r in range(tags.shape[0]):
            sets, ways = np.nonzero(tags[r] >= 0)
            rows = np.asarray(shards[r].data[0][sets, ways])
            per_rank.append((tags[r][sets, ways].astype(np.int64), rows))
        out.append(per_rank)
    return out


class StepRecorder:
    """Wraps the compiled step for the warm-up epoch.  For its first ``n``
    calls it keeps host copies of the minibatch, the step seed and loss,
    the weights before the first call, Adam's first moment after it, the
    weights after the ``n``-th, and (``caches``) the HEC's valid lines
    after each; later calls go straight through."""

    def __init__(self, step_fn, n: int = 3, caches: bool = False):
        self.step_fn, self.n, self.want_caches = step_fn, n, caches
        self.calls = 0
        self.mbs, self.seeds, self.losses, self.caches = [], [], [], []
        self.params0 = self.mu1 = self.params_n = None

    def __call__(self, params, opt_state, hec, hot, inflight, data, mb, seed,
                 *rest):
        import jax
        i = self.calls
        self.calls += 1
        if i >= self.n:
            return self.step_fn(params, opt_state, hec, hot, inflight, data,
                                mb, seed, *rest)
        if i == 0:
            self.params0 = jax.device_get(params)
        self.mbs.append(jax.device_get(mb))
        self.seeds.append(int(seed))
        out = self.step_fn(params, opt_state, hec, hot, inflight, data, mb,
                           seed, *rest)
        self.losses.append(float(out[-1]["loss"]))
        if i == 0:
            self.mu1 = jax.device_get(out[1]["mu"])
        if i == self.n - 1:
            self.params_n = jax.device_get(out[0])
        if self.want_caches:
            self.caches.append(read_cache(out[2]))
        return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What one run of a cell measured and kept for the check."""
    setup_s: float = 0.0
    compile_s: float = 0.0
    compiles: int = 0
    window_s: float = 0.0
    epochs: int = 0
    steps: int = 0
    losses: list = dataclasses.field(default_factory=list)
    epoch_walls: list = dataclasses.field(default_factory=list)
    phase_s: dict = dataclasses.field(default_factory=dict)
    compile_in_window_s: float = 0.0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    hit_rate_l0: float = 0.0
    push_rows: float = 0.0


PHASES = ("sample", "host_prep", "stage", "step")


def _annotated(step_fn):
    """The compiled step inside a ``bench_step`` host annotation, so that
    a trace shows when the trainer loop is in the step call; the window's
    idle gaps are named by what the host was doing."""
    import jax

    def call(*args):
        with jax.profiler.TraceAnnotation("bench_step"):
            return step_fn(*args)
    return call


def _peak(chips: int) -> int:
    from core.device import memory_peak_bytes
    return memory_peak_bytes(chips)


def run_cell(cfg, graph, ps, chips: int, seed: int, seconds: float,
             t_start: float, clock, trace_dir: str = "",
             record_steps: int = 3):
    """Set up, warm up (recording), then train whole epochs for
    ``seconds`` (no window where ``seconds <= 0``).  Returns ``(Run,
    StepRecorder)``; the program's device state is released first."""
    import jax
    from repro import obs
    from repro.launch.train import setup_gnn

    run = Run()
    dd, tr, state = setup_gnn(ps, cfg, seed=seed, mode="aep")
    step_fn = tr.make_step(dd)
    rec = StepRecorder(step_fn, n=record_steps, caches=chips > 1)
    state, hist = tr.train_epochs(ps, dd, state, 1, seed0=seed,
                                  step_fn=rec, start_epoch=0)
    jax.block_until_ready(state)
    run.setup_s = time.perf_counter() - t_start
    run.compile_s, run.compiles = clock.seconds, clock.count

    if seconds <= 0:                    # readings only: no window
        run.memory_peak_bytes = _peak(chips)
        del dd, tr, state, step_fn, hist
        rec.step_fn = None
        gc.collect()
        return run, rec
    reg = obs.get().registry
    ph0 = {p: reg.value("phase_seconds", phase=p) for p in PHASES}
    step0 = int(state["step"])
    c0, n0 = clock.seconds, clock.count
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    timed_step = _annotated(step_fn)
    t0 = time.perf_counter()
    window_hist = []
    with jax.profiler.TraceAnnotation("bench_window"):
        while True:
            t_ep = time.perf_counter()
            state, h = tr.train_epochs(ps, dd, state, 1, seed0=seed,
                                       step_fn=timed_step,
                                       start_epoch=1 + run.epochs)
            jax.block_until_ready(state)
            run.epoch_walls.append(time.perf_counter() - t_ep)
            window_hist += h
            run.epochs += 1
            if time.perf_counter() - t0 >= seconds:
                break
    run.window_s = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    run.steps = int(state["step"]) - step0
    run.compile_in_window_s = clock.seconds - c0
    run.compiles_in_window = clock.count - n0
    run.phase_s = {p: reg.value("phase_seconds", phase=p) - ph0[p]
                   for p in PHASES}
    run.losses = [e["loss"] for e in window_hist]
    run.hit_rate_l0 = float(np.mean([e.get("hec_hit_rate_l0", 0.0)
                                     for e in window_hist]))
    run.push_rows = float(np.mean([e.get("aep_push_rows", 0.0)
                                   for e in window_hist]))
    run.memory_peak_bytes = _peak(chips)
    del dd, tr, state, step_fn, timed_step, h, hist
    rec.step_fn = None
    gc.collect()
    return run, rec
