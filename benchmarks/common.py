"""Benchmark helpers: timing, CSV emission, and machine-readable result
artifacts.

Every suite's ``emit`` rows and its final ``result`` payload are recorded
under the active suite name (set by ``benchmarks/run.py``); at the end of
a run, ``write_artifacts`` writes one ``BENCH_<suite>.json`` per suite so
the perf trajectory is machine-readable across PRs (CI uploads the files
as a workflow artifact).

Recording is backed by the obs :class:`~repro.obs.registry.MetricsRegistry`
(its ordered event log + a ``bench_us`` histogram per suite) instead of a
private dict — one sink for runtime metrics and benchmark rows.  The
registry here is a dedicated always-on instance, so benchmarks record even
when the process-wide obs runtime is disabled, and the emitted
``BENCH_<suite>.json`` files are byte-identical to the pre-registry
format."""
from __future__ import annotations

import json
import os
import time

import jax

from repro import obs

_active: str | None = None
_registry = obs.MetricsRegistry(enabled=True)
_out_dir: str = "bench-artifacts"


def registry() -> obs.MetricsRegistry:
    """The benchmark recorder's registry (always enabled)."""
    return _registry


def cpu_child_env() -> dict:
    """Environment for a suite's JAX child process: the repo's ``src`` on
    ``PYTHONPATH`` and the CPU backend pinned (``JAX_PLATFORMS=cpu``).  The
    runner has touched JAX before the child starts, so on a TPU host the
    parent holds the chip and a child that reached for it would fail or
    hang on the chip's lock.  Chip-side work stays in one process."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def set_out_dir(path: str):
    """Where ``write_artifacts``/``artifact_path`` place files."""
    global _out_dir
    _out_dir = path


def artifact_path(filename: str) -> str:
    """Absolute path for an extra artifact (trace files etc.) in the
    benchmark output directory (created on demand; CI uploads the dir)."""
    os.makedirs(_out_dir, exist_ok=True)
    return os.path.join(_out_dir, filename)


def time_fn(fn, *args, warmup=2, iters=10):
    """Median wall time per call in microseconds (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def begin_suite(name: str):
    """Route subsequent ``emit``/``result`` calls to this suite's record."""
    global _active
    _active = name
    _registry.log_event("suite_begin", suite=name)


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}")
    if _active is not None:
        _registry.histogram("bench_us", suite=_active).observe(us)
        _registry.log_event("bench_row", suite=_active, name=name,
                            us_per_call=us, derived=derived)


def result(payload: dict):
    """Print the suite's ``RESULT{...}`` line AND record the payload for
    the JSON artifact (replaces the bare ``print("RESULT"+json.dumps)``)."""
    print("RESULT" + json.dumps(payload))
    if _active is not None:
        _registry.log_event("bench_result", suite=_active, payload=payload)


def _suite_records() -> dict:
    """Rebuild ``{suite: {"rows": [...], "result": ...}}`` from the
    registry's ordered event log (insertion order preserved)."""
    suites: dict = {}
    for ev in _registry.events:
        kind = ev["kind"]
        if kind == "suite_begin":
            suites.setdefault(ev["suite"], {"rows": [], "result": None})
        elif kind == "bench_row":
            suites.setdefault(ev["suite"], {"rows": [], "result": None})
            suites[ev["suite"]]["rows"].append(
                {"name": ev["name"], "us_per_call": ev["us_per_call"],
                 "derived": ev["derived"]})
        elif kind == "bench_result":
            suites.setdefault(ev["suite"], {"rows": [], "result": None})
            suites[ev["suite"]]["result"] = ev["payload"]
    return suites


def write_artifacts(out_dir: str | None = None) -> list:
    """One ``BENCH_<suite>.json`` per recorded suite; returns the paths."""
    out_dir = out_dir or _out_dir
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for name, rec in _suite_records().items():
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump({"suite": name, **rec}, f, indent=2, sort_keys=True)
            f.write("\n")
        paths.append(path)
    return paths
