"""Unified observability subsystem: metrics registry, phase-span tracing,
and the paper-style epoch breakdown.

One process-wide :class:`Observability` runtime (swap it with
``configure``) owns

  * a :class:`MetricsRegistry` — counters / gauges / histograms (exact
    window p50/p99/max) labeled by rank/layer/subsystem; the single sink
    for the trainer's step counters, both serve schedulers' latency
    stats, the HEC/hot-tier cache counters, and the benchmark suite
    recorder.  **Default on** (cheap python-side accumulation; never
    touches device numerics),
  * a :class:`Tracer` — ``span("sample") / span("stage") / span("fwd") /
    span("aep_push") / span("bwd") / span("serve_round")`` phase spans
    with per-rank thread-aware nesting, exported as Chrome trace-event
    JSON (load in chrome://tracing / Perfetto).  **Opt-in**
    (``ObsConfig(trace=True)`` or ``--trace-out`` on the launchers),
  * the ``jax.profiler`` host plane: every span is also a
    ``jax.profiler.TraceAnnotation`` for its lifetime, so a profiler trace
    (``jax.profiler.start_trace``, TensorBoard / Perfetto) shows it by
    name on the thread that ran it, on the same clock as the device ops.
    Outside a profiler trace the annotation costs one enter/exit,
  * the :class:`EpochBreakdown` / :class:`StepModel` report: per-epoch
    sample / host-prep / H2D / forward / AEP-push / backward shares and
    the overlap-efficiency figure (fraction of modeled push latency
    hidden behind the backward pass),
  * the **cluster health plane** (:mod:`repro.obs.cluster` /
    :mod:`repro.obs.detect` / :mod:`repro.obs.sentinel`): per-rank
    telemetry shards aggregated into rank-labeled series + skew/sum
    cluster views, straggler / load-skew / edge-cut-drift / SLO-burn /
    hot-tier-decay detectors, and the bounded flight recorder that dumps
    ``FLIGHT_<reason>.json`` on a detection or an escaped exception
    (:class:`HealthPlane`, wired via ``DistTrainer(health=...)`` and the
    serve schedulers' ``health=`` argument),
  * the **embedding quality plane** (:mod:`repro.obs.quality`): per-layer
    HEC/hot-tier staleness-age histograms, the online exactness audit
    (sampled cached embeddings vs exact offline recomputation, relative
    L2), and the per-epoch convergence series — plus the
    :class:`QualityBudgetDetector` that dumps ``FLIGHT_quality.json``
    when audit error persists over budget (:class:`QualityPlane`, wired
    via ``DistTrainer(quality=...)`` / the schedulers' ``quality=``
    argument; audit armed with ``--audit-interval``).

Instrumented code calls the module-level helpers::

    from repro import obs
    with obs.span("sample", epoch=ep, step=k):
        ...
    obs.count("halo_fetched", n, subsystem="serve")

With everything disabled (``ObsConfig(enabled=False)``) every helper
short-circuits to shared no-op objects: zero allocation per call, no
profiler annotation, and —
because observability only ever *reads* timings and host counters — the
computed outputs are bit-identical with obs on, off, or tracing
(pinned in ``tests/test_obs.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax

from repro.obs.breakdown import (EpochBreakdown, MEASURED_PHASES,  # noqa: F401
                                 REPORT_PHASES, StepModel)
from repro.obs.cluster import (RankAccumulator, SeriesView,  # noqa: F401
                               publish_rank_series, rank_series, skew_ratio)
from repro.obs.detect import (Detection, EdgeCutDriftDetector,  # noqa: F401
                              HotTierDecayDetector, LoadSkewDetector,
                              QualityBudgetDetector, SLOBurnDetector,
                              StragglerDetector)
from repro.obs.quality import (AuditReport, QualityConfig,  # noqa: F401
                               QualityPlane, relative_l2)
from repro.obs.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                MetricsRegistry, PromFileWriter,
                                hit_rate_metrics)
from repro.obs.sentinel import (FlightRecorder, HealthConfig,  # noqa: F401
                                HealthPlane)
from repro.obs.tracing import Tracer, validate_chrome_trace  # noqa: F401


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability runtime configuration.

    ``enabled`` gates the metrics registry (counters/histograms/phase
    timers — default on); ``trace`` gates span tracing (default off,
    opt-in: it buffers one event per span).  ``trace_path`` /
    ``metrics_path`` are written by ``flush()`` (the launchers'
    ``--trace-out`` plumbing)."""
    enabled: bool = True
    trace: bool = False
    trace_path: Optional[str] = None
    metrics_path: Optional[str] = None
    window: int = 8192            # histogram sample window
    rank: int = 0                 # trace pid (one process == one rank here)


class _NullSpan:
    """Shared no-op context manager returned when obs is fully disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _PhaseSpan:
    """Times one phase: accumulates ``phase_seconds{phase=<name>}`` in the
    registry (when enabled), records a trace event (when tracing), and
    holds a ``jax.profiler.TraceAnnotation`` of the same name open, so a
    profiler trace shows the phase on the thread that ran it."""
    __slots__ = ("_obs", "_name", "_args", "_t0", "_annotation")

    def __init__(self, runtime: "Observability", name: str, args: dict):
        self._obs = runtime
        self._name = name
        self._args = args

    def __enter__(self):
        if self._obs.tracer.enabled:
            self._obs.tracer.push(self._name)
        # the annotation nests inside the timed interval, so the profiler's
        # span never outlasts the registry's
        self._t0 = time.perf_counter()
        self._annotation = jax.profiler.TraceAnnotation(self._name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        t1 = time.perf_counter()
        o = self._obs
        if o.registry.enabled:
            o.registry.counter("phase_seconds",
                               phase=self._name).inc(t1 - self._t0)
            o.registry.counter("phase_calls", phase=self._name).inc(1)
        if o.tracer.enabled:
            o.tracer.record(self._name, self._t0, t1, args=self._args)
        return False


class Observability:
    """The runtime: one registry + one tracer (+ flush plumbing)."""

    def __init__(self, cfg: Optional[ObsConfig] = None):
        self.cfg = cfg or ObsConfig()
        self.registry = MetricsRegistry(enabled=self.cfg.enabled,
                                        window=self.cfg.window)
        self.tracer = Tracer(enabled=self.cfg.trace, rank=self.cfg.rank)

    def span(self, name: str, **args):
        if not (self.registry.enabled or self.tracer.enabled):
            return _NULL_SPAN
        return _PhaseSpan(self, name, args)

    def count(self, name: str, amount=1.0, **labels):
        self.registry.counter(name, **labels).inc(amount)

    def observe(self, name: str, value: float, **labels):
        self.registry.histogram(name, **labels).observe(value)

    def set_gauge(self, name: str, value: float, **labels):
        self.registry.gauge(name, **labels).set(value)

    def phase_seconds(self, phase: str) -> float:
        """Accumulated seconds of one phase (0.0 while disabled)."""
        return self.registry.value("phase_seconds", phase=phase)

    def flush(self) -> List[str]:
        """Write the configured trace/metrics files; returns paths."""
        paths = []
        if self.cfg.trace_path and self.tracer.enabled:
            paths.append(self.tracer.write(self.cfg.trace_path))
        if self.cfg.metrics_path and self.registry.enabled:
            paths.append(self.registry.write_jsonl(self.cfg.metrics_path))
        return paths


_runtime = Observability()


def get() -> Observability:
    """The active process-wide runtime."""
    return _runtime


def configure(cfg: Optional[ObsConfig] = None) -> Observability:
    """Install (and return) a fresh runtime; ``configure()`` restores the
    defaults (counters on, tracing off)."""
    global _runtime
    _runtime = Observability(cfg)
    return _runtime


# -- module-level helpers (proxy to the active runtime) ----------------------
def span(name: str, **args):
    return _runtime.span(name, **args)


def count(name: str, amount=1.0, **labels):
    _runtime.count(name, amount, **labels)


def observe(name: str, value: float, **labels):
    _runtime.observe(name, value, **labels)


def set_gauge(name: str, value: float, **labels):
    _runtime.set_gauge(name, value, **labels)


def flush() -> List[str]:
    return _runtime.flush()
